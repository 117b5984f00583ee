"""Test fixtures: synthetic clusters and trace replay.

Analog of the reference's load-bearing fixtures (SURVEY.md section 4): fake
clientset (client.ObjectStore is already in-process), scheduler-framework harness,
and workload generators standing in for the `examples/spark-jobs` colocation traces.
"""

from koordinator_tpu.testing.synth import (  # noqa: F401
    SynthCluster,
    synth_cluster,
    synth_full_cluster,
    top_up_running_pods,
)
