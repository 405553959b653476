"""What the rooflines of one kind of layer share: the least time the chip
could take for that kind's products of one step (forward and backward, from
shapes: a MAC function of the configuration's own ``.py``, recomputation
not counted) at the bf16 peak, over the device time a step under the
layer's scope, whatever implements it.  The layers are counted from the
configuration's layer list (``layer_types``' entries of the kind up to
``num_hidden_layers``, or ``hybrid_override_pattern``'s letter); sizes and
length come from the driver's ``obs``."""
from perfbench import manifest, opcount, scope_view


def layer_count(sizes, kind, letter):
    """Layers of ``kind`` in ``layer_types`` (up to ``num_hidden_layers``),
    else ``letter``s in ``hybrid_override_pattern``."""
    if "layer_types" in sizes:
        return sizes["layer_types"][:sizes["num_hidden_layers"]].count(kind)
    return sizes.get("hybrid_override_pattern", "").count(letter)


def read(obs, in_scope, macs, kind, letter):
    """The share in %, or ``None`` where the run has no device trace, the
    driver states no sizes, the scope took no time, no layer is of ``kind``
    or the configuration has no ``macs`` function.  ``in_scope(classes)``
    says whether a ``scope_view`` row's block classes are the layer's."""
    view = scope_view.traced(obs)
    sizes, mix = obs.get("sizes"), obs.get("mix")
    if not view or not view["steps"] or not sizes or not mix:
        return None
    busy_s = sum(r["s"] for r in view["rows"] if in_scope(r["classes"]))
    cfg = manifest.load_module("configs", sizes["name"])
    layers = layer_count(sizes, kind, letter)
    if busy_s <= 0 or not layers or not hasattr(cfg, macs):
        return None
    ops = opcount.train_ops(getattr(cfg, macs)(sizes, mix["seq_len"])) \
        * layers * obs["batch"] / obs["chips"]
    return 100.0 * (ops / obs["peak"]["bf16_flops_per_s"]) \
        / (busy_s / view["steps"])
