"""Kernels: the least time the chip could take for the held gated experts'
three products of one step, forward and backward, over the device time a
step under their scope (``MoEExperts``).  The rows are those ACTUALLY held
in the traced steps (the program's ``moe.rows_held`` over ``moe.steps``,
counted on the device and read by the driver before and after those steps):
rows a grouped product pads or skips are not work, nor is the forward that
the backward pass computes again.  The sizes are the cell's own, from the
driver's ``obs``."""
from perfbench import manifest, opcount, scope_view

PRODUCTS = "MoEExperts"


def read(obs):
    view = scope_view.traced(obs)
    sizes, counts = obs.get("sizes"), obs.get("moe_traced") or {}
    if not view or not view["steps"] or not sizes \
            or not counts.get("moe.steps"):
        return None
    busy_s = sum(r["s"] for r in view["rows"] if PRODUCTS in r["classes"])
    if busy_s <= 0:
        return None
    layers = sizes["num_hidden_layers"] - sizes["first_k_dense_replace"] \
        + sizes["num_nextn_predict_layers"]
    rows_a_step = counts["moe.rows_held"] / (counts["moe.steps"] / layers)
    cfg = manifest.load_module("configs", sizes["name"])
    ops = opcount.train_ops(rows_a_step * cfg.expert_row_macs(sizes))
    return 100.0 * (ops / obs["chips"] / obs["peak"]["bf16_flops_per_s"]) \
        / (busy_s / view["steps"])
