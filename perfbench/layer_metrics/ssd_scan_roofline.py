"""Kernels: the least time the chip could take for the scan's chunk products
of one step (forward and backward, from shapes: the configuration's
``scan_macs``, recomputation not counted) at the bf16 peak, over the device
time a step under the scan's scope, whatever implements the scan.  The scan
is elementwise and reduction work around small products, so this reads low:
it is the distance to a matrix unit the scan cannot fill, and says by how
much a kernel that kept the decay masks in VMEM could shorten it.  The sizes
and the sequence length are the cell's own, from the driver's ``obs``."""
from perfbench import manifest, opcount, scope_view

SCAN = "SsdScan"


def read(obs):
    view = scope_view.traced(obs)
    sizes, mix = obs.get("sizes"), obs.get("mix")
    if not view or not view["steps"] or not sizes or not mix:
        return None
    busy_s = sum(r["s"] for r in view["rows"] if SCAN in r["classes"])
    if busy_s <= 0:
        return None
    cfg = manifest.load_module("configs", sizes["name"])
    layers = sizes["hybrid_override_pattern"].count("M")
    ops = opcount.train_ops(cfg.scan_macs(sizes, mix["seq_len"])) \
        * layers * obs["batch"] / obs["chips"]
    return 100.0 * (ops / obs["peak"]["bf16_flops_per_s"]) \
        / (busy_s / view["steps"])
