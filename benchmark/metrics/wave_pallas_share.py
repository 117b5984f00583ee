"""Kernels: the share of the window's chained ``kernel`` spans
(``overlap="1"``, one per chained dispatch) whose ``backend`` attribute
is ``pallas``, in %: 100 where every chain's search pass ran the Pallas
full-chain kernel, 0 where each stayed on the XLA evaluator. A program
whose chained kernel spans carry no ``backend``, or a window with no
chained dispatch, reads nothing."""


def read(run):
    chained = [k for root in run.spans for k in root.find_all("kernel")
               if k.attributes.get("overlap") == "1"]
    if not any("backend" in k.attributes for k in chained):
        return None
    pallas = sum(1 for k in chained
                 if k.attributes.get("backend") == "pallas")
    return 100.0 * pallas / len(chained)
