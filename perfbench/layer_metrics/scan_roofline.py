"""Kernels: the least time the chip could take for the Mamba-2 scan's chunk
products of one step (the configuration's own ``scan_macs``) at the bf16
peak, over the device time a step under the scan's scope (``SsdScan``),
whatever implements the scan; the layers are the configuration's ``mamba``
entries or ``M`` letters (``perfbench.layer_roofline``).  The scan's
products are one chunk tall (``chunk_size`` or ``mamba_chunk_size`` rows)
between elementwise decay masks, so this reads low: it is the distance to
a matrix unit the scan cannot fill."""
from perfbench import layer_roofline


def read(obs):
    return layer_roofline.read(obs, lambda classes: "SsdScan" in classes,
                               "scan_macs", "mamba", "M")
