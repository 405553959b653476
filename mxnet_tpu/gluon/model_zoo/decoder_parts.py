"""Parts the decoder models of this zoo share: the SiLU-gated feed-forward,
the residual stream's type under AMP, and a stack of layers run with every
layer recomputed in the backward pass.  A model subclasses
:class:`GatedMLP` under a name of its own, so that its device scope is the
model's."""
from __future__ import annotations

from ... import amp
from ...ndarray.ndarray import invoke
from ..block import HybridBlock, remat_call
from ..nn import Dense

__all__ = ["GatedMLP", "low_stream", "run_layers"]


class GatedMLP(HybridBlock):
    """``down_proj(silu(gate x) * (up x))``; ``gate_up_proj`` gives gate and
    up side by side in one product, the gate first."""

    def __init__(self, hidden_size, intermediate_size, init, out_init):
        super().__init__()
        self._width = intermediate_size
        self.gate_up_proj = Dense(2 * intermediate_size, use_bias=False,
                                  flatten=False, in_units=hidden_size,
                                  weight_initializer=init)
        self.down_proj = Dense(hidden_size, use_bias=False, flatten=False,
                               in_units=intermediate_size,
                               weight_initializer=out_init)

    def forward(self, x):
        both = self.gate_up_proj(x)
        gate = both.slice_axis(axis=-1, begin=0, end=self._width)
        up = both.slice_axis(axis=-1, begin=self._width, end=None)
        return self.down_proj(gate * invoke("sigmoid", [gate], {}) * up)


def low_stream(x):
    """The residual stream follows the activations' type under AMP."""
    low = amp.target_dtype()
    return x if low is None else x.astype(low)


def run_layers(layers, x, recompute):
    """``layers(x)``, or with ``recompute`` each layer one
    ``remat_call`` segment."""
    if not recompute:
        return layers(x)
    for layer in layers:
        x = remat_call([layer], x)
    return x
