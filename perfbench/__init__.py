"""perfbench — the benchmark of mxnet_tpu (see perfbench/README.md).

The yardstick lives here: traffic generation, operation counts from
shapes, the table of peaks, the reduction from the device trace to
metrics, each configuration's plain reference and the comparison that
decides ``correct``.  From the program it takes the system under test and
its counters.  Importing this package touches neither jax nor mxnet_tpu.
"""
