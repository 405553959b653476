"""Kernels: the least time the chip could take for the gated delta rule's
chunk products of one step (forward and backward, from shapes: the
configuration's ``delta_rule_macs``, recomputation and the triangular
inverse not counted) at the bf16 peak, over the device time a step under the
rule's scope (``DeltaRule``), whatever implements the rule.  A chunk's
products are 64 rows tall between elementwise decay masks and a sequential
carry, so this reads low: it is the distance to a matrix unit that XLA's
form of the rule cannot fill, and says by how much a kernel that kept a
chunk in VMEM could shorten it.  The sizes and the sequence length are the
cell's own, from the driver's ``obs``."""
from perfbench import manifest, opcount, scope_view

RULE = "DeltaRule"


def read(obs):
    view = scope_view.traced(obs)
    sizes, mix = obs.get("sizes"), obs.get("mix")
    if not view or not view["steps"] or not sizes or not mix:
        return None
    busy_s = sum(r["s"] for r in view["rows"] if RULE in r["classes"])
    if busy_s <= 0:
        return None
    cfg = manifest.load_module("configs", sizes["name"])
    layers = sizes["layer_types"][:sizes["num_hidden_layers"]] \
        .count("linear_attention")
    ops = opcount.train_ops(cfg.delta_rule_macs(sizes, mix["seq_len"])) \
        * layers * obs["batch"] / obs["chips"]
    return 100.0 * (ops / obs["peak"]["bf16_flops_per_s"]) \
        / (busy_s / view["steps"])
