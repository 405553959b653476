"""Kernels: the least time the chip could take for the causal score and
value products of every latent-attention core of one step (forward and
backward, from shapes: the configuration's ``latent_core_macs``, 20 heads x
(256 + 256) at half the square; recomputation not counted) at the bf16 peak,
over the device time a step under the core's scope (``LatentCore``).  The
core is ``pallas_kernels.flash_attention_gqa`` at this shape, whose forward
runs a second time under ``recompute_layers`` and whose one backward call
computes the scores again: 9 products are run for the 6 counted (2 forward,
2 recomputed, 5 backward), so about 67 is the kernels at full speed.  The
sizes and the sequence length are the cell's own, from the driver's
``obs``."""
from perfbench import manifest, opcount, scope_view

CORE = "LatentCore"


def read(obs):
    view = scope_view.traced(obs)
    sizes, mix = obs.get("sizes"), obs.get("mix")
    if not view or not view["steps"] or not sizes or not mix:
        return None
    busy_s = sum(r["s"] for r in view["rows"] if CORE in r["classes"])
    if busy_s <= 0:
        return None
    cfg = manifest.load_module("configs", sizes["name"])
    layers = sizes["num_hidden_layers"] + sizes["num_nextn_predict_layers"]
    ops = opcount.train_ops(cfg.latent_core_macs(sizes, mix["seq_len"])) \
        * layers * obs["batch"] / obs["chips"]
    return 100.0 * (ops / obs["peak"]["bf16_flops_per_s"]) \
        / (busy_s / view["steps"])
