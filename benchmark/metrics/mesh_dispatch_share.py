"""Dispatch policy: the share of the window's ``kernel`` spans (device
dispatches) whose ``shards`` attribute is above 1, in %: 100 where every
dispatch ran over the mesh, lower where one fell back to one chip. A
program whose kernel spans carry no ``shards`` reads nothing."""


def read(run):
    kernels = [k for root in run.spans for k in root.find_all("kernel")]
    if not any("shards" in k.attributes for k in kernels):
        return None
    meshed = sum(1 for k in kernels
                 if int(k.attributes.get("shards", "1")) > 1)
    return 100.0 * meshed / len(kernels)
