"""The second expert path (``parallel.moe.held_experts_layer``, op
``held_experts``, block ``NemotronHMoE``): sorted, without drops, told which
experts it holds.  CPU, small sizes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon.model_zoo import nemotron_h as nh
from mxnet_tpu.parallel import moe

K, SCALING = 3, 2.5


def _weights(tokens=64, width=16, hidden=24, experts=16, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return dict(
        x=jax.random.normal(ks[0], (tokens, width)),
        router=0.5 * jax.random.normal(ks[1], (experts, width)),
        bias=0.05 * jax.random.normal(ks[2], (experts,)),
        up=0.2 * jax.random.normal(ks[3], (experts, width, hidden)),
        down=0.2 * jax.random.normal(ks[4], (experts, hidden, width)))


def _loop_over_experts(w, k=K):
    """Every expert's weighted part of the uncut layer, one expert at a
    time under a dense mask: ``parts[e]`` is (tokens, width)."""
    s = jax.nn.sigmoid(jnp.einsum("tm,em->te", w["x"], w["router"],
                                  precision="highest"))
    _, chosen = jax.lax.top_k(s + w["bias"], k)
    picked = jnp.take_along_axis(s, chosen, -1)
    weight = picked / picked.sum(-1, keepdims=True) * SCALING
    parts = []
    for e in range(w["router"].shape[0]):
        mine = jnp.sum(jnp.where(chosen == e, weight, 0.0), -1)
        h = jnp.square(jax.nn.relu(w["x"] @ w["up"][e]))
        parts.append(mine[:, None] * (h @ w["down"][e]))
    return parts, chosen


def _held(w, held, k=K, capacity_factor=4.0):
    ids = jnp.asarray(held)
    return moe.held_experts_layer(
        w["x"], w["router"], w["bias"], w["up"][ids], w["down"][ids],
        held=held, k=k, scaling=SCALING, capacity_factor=capacity_factor)


@pytest.mark.parametrize("held", [(0, 1, 2, 3), (4, 9, 15), (7,)])
def test_held_part_equals_the_loop_over_those_experts(held):
    w = _weights()
    parts, chosen = _loop_over_experts(w)
    out, stats = _held(w, held)
    np.testing.assert_allclose(out, sum(parts[e] for e in held), atol=1e-5)
    stats = dict(zip(moe.HELD_STATS, np.asarray(stats)))
    assert stats["rows_routed"] == 64 * K and stats["steps"] == 1
    assert stats["rows_held"] == int(jnp.sum(jnp.isin(chosen,
                                                      jnp.asarray(held))))
    assert stats["rows_overflow"] == 0


def test_the_shares_add_up_to_the_uncut_layer():
    """Sixteen ranks, each told its 8 of 128 experts: their routed parts,
    and what every rank computes alike (the shared expert) counted ONCE,
    equal the uncut reference layer."""
    w = _weights(tokens=48, experts=128, seed=3)
    parts, _ = _loop_over_experts(w, k=6)
    shared = jnp.square(jax.nn.relu(w["x"] @ w["up"][0])) @ w["down"][0]
    whole = sum(parts) + shared
    total = shared
    for rank in range(16):
        out, stats = _held(w, tuple(range(8 * rank, 8 * rank + 8)), k=6)
        assert float(stats[moe.HELD_STATS.index("rows_overflow")]) == 0
        total = total + out
    np.testing.assert_allclose(total, whole, atol=2e-5)


def test_no_row_is_lost_at_four_times_the_mean_load_nor_with_empty_experts():
    """A bias that sends every token to expert 0: it gets tokens rows
    against a mean of tokens * k / experts = tokens / 4, and experts 2 and 3
    get none.  Nothing is dropped while the buffer holds."""
    w = _weights(tokens=40, experts=8, seed=5)
    w["bias"] = w["bias"].at[0].set(10.0).at[2].set(-10.0).at[3].set(-10.0)
    parts, chosen = _loop_over_experts(w, k=2)
    assert bool(jnp.all(jnp.any(chosen == 0, -1)))
    assert not bool(jnp.any((chosen == 2) | (chosen == 3)))
    held = (0, 1, 2, 3)
    out, stats = _held(w, held, k=2, capacity_factor=2.0)
    stats = dict(zip(moe.HELD_STATS, np.asarray(stats)))
    assert stats["load_max"] == 40 and stats["load_max"] / (40 * 2 / 8) == 4
    assert stats["rows_overflow"] == 0
    np.testing.assert_allclose(out, sum(parts[e] for e in held), atol=1e-5)
    # gradients reach the busy expert and are zero for the empty ones
    ids = jnp.asarray(held)
    g = jax.grad(lambda up: jnp.sum(moe.held_experts_layer(
        w["x"], w["router"], w["bias"], up, w["down"][ids], held=held, k=2,
        scaling=SCALING, capacity_factor=2.0)[0]))(w["up"][ids])
    assert float(jnp.max(jnp.abs(g[0]))) > 0
    assert float(jnp.max(jnp.abs(g[2:]))) == 0


def test_rows_beyond_the_buffer_are_counted_not_silently_lost():
    w = _weights(tokens=400, experts=8, seed=5)
    w["bias"] = w["bias"].at[0].set(10.0)
    # mean share of 2 held of 8 at k=2: 200 rows, a buffer of 256; expert
    # 0 alone gets 400
    rows = moe.held_buffer_rows(400, 2, 8, 2, 1.0)
    assert rows == 256
    out, stats = _held(w, (0, 1), k=2, capacity_factor=1.0)
    stats = dict(zip(moe.HELD_STATS, np.asarray(stats)))
    assert stats["rows_held"] > rows
    assert stats["rows_overflow"] == stats["rows_held"] - rows
    assert bool(jnp.all(jnp.isfinite(out)))


def test_gradients_equal_the_loops():
    w = _weights(tokens=32, seed=7)
    held = (0, 1, 2, 3)
    ids = jnp.asarray(held)

    def ours(x, up, router):
        return jnp.sum(jnp.sin(moe.held_experts_layer(
            x, router, w["bias"], up[ids], w["down"][ids], held=held, k=K,
            scaling=SCALING, capacity_factor=4.0)[0]))

    def loop(x, up, router):
        parts, _ = _loop_over_experts({**w, "x": x, "up": up,
                                       "router": router})
        return jnp.sum(jnp.sin(sum(parts[e] for e in held)))

    args = (w["x"], w["up"], w["router"])
    for got, want in zip(jax.grad(ours, argnums=(0, 1, 2))(*args),
                         jax.grad(loop, argnums=(0, 1, 2))(*args)):
        np.testing.assert_allclose(got, want, atol=2e-5)


def test_a_wrong_held_list_is_refused():
    w = _weights()
    with pytest.raises(ValueError, match="held="):
        _held(w, (3, 1))
    with pytest.raises(ValueError, match="held="):
        moe.held_experts_layer(w["x"], w["router"], w["bias"], w["up"][:3],
                               w["down"][:3], held=(0, 1), k=K)


def test_bf16_activations_keep_a_float32_router():
    """The products take the activations' type; the choices are made in
    float32 at the highest precision from whatever arrives."""
    w = _weights(seed=9)
    x16 = w["x"].astype(jnp.bfloat16)
    out, _ = moe.held_experts_layer(
        x16, w["router"], w["bias"], w["up"][:4], w["down"][:4],
        held=(0, 1, 2, 3), k=K, scaling=SCALING, capacity_factor=4.0)
    assert out.dtype == jnp.bfloat16
    parts, _ = _loop_over_experts({**w, "x": x16.astype(jnp.float32)})
    want = sum(parts[:4])
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - want))) \
        < 0.03 * float(jnp.max(jnp.abs(want)))


# -- the grouped products' kernel (Pallas interpreter) --------------------------
def test_grouped_matmul_equals_a_product_a_tile(monkeypatch):
    from mxnet_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "GROUP_TILE", 8)
    groups = jnp.asarray([0, 0, 1, 2, 2, 2, 2], jnp.int32)
    used = jnp.asarray([5], jnp.int32)          # the last two tiles: skipped
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (56, 256))
    w = jax.random.normal(ks[1], (3, 256, 128))
    ct = jax.random.normal(ks[2], (56, 128))

    def want(x, w):
        out = jnp.einsum("tmk,tkn->tmn", x.reshape(7, 8, 256), w[groups],
                         precision="highest")
        return (out * (jnp.arange(7) < 5)[:, None, None]).reshape(56, 128)

    np.testing.assert_allclose(pk.grouped_matmul(x, w, groups, used),
                               want(x, w), atol=1e-4)
    got = jax.grad(lambda x, w: jnp.sum(pk.grouped_matmul(
        x, w, groups, used) * ct), argnums=(0, 1))(x, w)
    ref = jax.grad(lambda x, w: jnp.sum(want(x, w) * ct),
                   argnums=(0, 1))(x, w)
    for g, r in zip(got, ref):
        np.testing.assert_allclose(g, r, atol=1e-3)
    with pytest.raises(ValueError, match="grouped_matmul cannot take"):
        pk.grouped_matmul(x[:50], w, groups, used)


@pytest.mark.parametrize("bias0", [0.0, 10.0])
def test_the_kernel_path_equals_the_loop_over_experts(monkeypatch, bias0):
    """The layout a TPU trace takes (every expert's rows start on a tile,
    every expert owns one; ``bias0`` sends every token to expert 0 and
    leaves experts without a row) against the loop, values and gradients."""
    from mxnet_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "GROUP_TILE", 8)
    monkeypatch.setattr(moe, "_grouped_platform", lambda: "tpu")
    w = _weights(tokens=96, width=128, hidden=40, experts=16, seed=11)
    w["router"] = 0.4 * w["router"]
    w["bias"] = w["bias"].at[0].add(bias0)
    held = (0, 1, 2, 3)
    ids = jnp.asarray(held)
    parts, _ = _loop_over_experts(w)
    out, stats = _held(w, held)
    np.testing.assert_allclose(out, sum(parts[e] for e in held), atol=1e-4)
    assert float(stats[moe.HELD_STATS.index("rows_overflow")]) == 0

    def ours(x, up, down):
        return jnp.sum(jnp.sin(moe.held_experts_layer(
            x, w["router"], w["bias"], up[ids], down[ids], held=held, k=K,
            scaling=SCALING, capacity_factor=4.0)[0]))

    def loop(x, up, down):
        parts, _ = _loop_over_experts({**w, "x": x, "up": up, "down": down})
        return jnp.sum(jnp.sin(sum(parts[e] for e in held)))

    args = (w["x"], w["up"], w["down"])
    for got, want in zip(jax.grad(ours, argnums=(0, 1, 2))(*args),
                         jax.grad(loop, argnums=(0, 1, 2))(*args)):
        np.testing.assert_allclose(got, want, atol=2e-3)


# -- the kernels' blocking: what a tile behind ``tiles_used`` costs --------------
# tiles' groups and tiles_used: every group ONE used tile, and groups of several
_LAYOUTS = {"one": ([0, 1, 2, 2, 2], 3), "several": ([0, 0, 0, 1, 2, 2, 2, 2], 6)}
# (K, N) in the proportions of the two forms' first products: relu2 narrows
# (2688 -> 1920), the gated one widens (2048 -> gate and up, 3072)
_WIDTHS = {"relu2": (384, 256), "silu": (256, 384)}


@pytest.mark.parametrize("resident", [True, False],
                         ids=["whole_matrix", "blocks"])
@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
@pytest.mark.parametrize("act", sorted(_WIDTHS))
def test_rows_behind_tiles_used_are_never_read(monkeypatch, act, layout,
                                               resident):
    """NaN in every row behind ``tiles_used``, operands and cotangent: zeros
    come out there, and what lies in front is finite and right, values and
    both gradients; with a group's whole matrix resident and (a VMEM budget
    of a fraction of it) cut into column blocks and row blocks."""
    from mxnet_tpu.ops import pallas_kernels as pk

    tile, (k, n) = 8, _WIDTHS[act]
    monkeypatch.setattr(pk, "GROUP_TILE", tile)
    if not resident:
        monkeypatch.setattr(pk, "_GROUP_VMEM", k * n)
        assert pk._group_cols(k, n, 4) == 128 < n
        assert pk._group_rows(k, n, 4) == 128 < k
    else:
        assert pk._group_cols(k, n, 4) == n and pk._group_rows(k, n, 4) == k
    groups, used = _LAYOUTS[layout]
    tiles, front = len(groups), used * tile
    groups = jnp.asarray(groups, jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(ks[0], (tiles * tile, k)).at[front:].set(jnp.nan)
    w = jax.random.normal(ks[1], (3, k, n))
    ct = jax.random.normal(ks[2], (tiles * tile, n)).at[front:].set(jnp.nan)

    def ours(x, w):
        return pk.grouped_matmul(x, w, groups, jnp.asarray([used], jnp.int32))

    def want(x, w):
        return jnp.einsum("tmk,tkn->tmn", x[:front].reshape(used, tile, k),
                          w[groups[:used]],
                          precision="highest").reshape(front, n)

    out, vjp = jax.vjp(ours, x, w)
    dx, dw = vjp(ct)
    ref, ref_vjp = jax.vjp(want, x, w)
    ref_dx, ref_dw = ref_vjp(ct[:front])
    for got, ref_front in ((out, ref), (dx, ref_dx[:front])):
        assert bool(jnp.all(got[front:] == 0))
        np.testing.assert_allclose(got[:front], ref_front, atol=1e-3)
    np.testing.assert_allclose(dw, ref_dw, atol=1e-3)


@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
def test_a_tile_behind_tiles_used_takes_the_last_used_tiles_blocks(layout):
    """The index maps themselves: a tile at or behind ``tiles_used`` names
    the operand blocks of the last used tile, whatever the outer index, so
    the pipeline has nothing to fetch for it; its output block is its own
    (zeros are written there), and a group's matrix block does not change
    over the group's tiles (fetched once a group)."""
    from mxnet_tpu.ops import pallas_kernels as pk

    groups, used = _LAYOUTS[layout]
    g, u = np.asarray(groups, np.int32), np.asarray([used], np.int32)
    operands = (pk._gmm_x_map, pk._gmm_w_map, pk._gmm_wt_map,
                pk._tgmm_x_map, pk._tgmm_dy_map)

    def blocks(fn, outer, i):
        return tuple(int(v) for v in fn(outer, i, g, u))

    for outer in (0, 2):
        for i in range(used, len(groups)):
            for fn in operands:
                assert blocks(fn, outer, i) == blocks(fn, outer, used - 1)
            assert blocks(pk._gmm_out_map, outer, i) == (i, outer)
            assert blocks(pk._tgmm_dw_map, outer, i) == (groups[i], outer, 0)
        for i in range(1, used):
            same = groups[i] == groups[i - 1]
            for fn in (pk._gmm_w_map, pk._gmm_wt_map, pk._tgmm_dw_map):
                assert (blocks(fn, outer, i) == blocks(fn, outer, i - 1)) \
                    == same
            assert blocks(pk._gmm_x_map, outer, i) == (i, 0)
            assert blocks(pk._tgmm_x_map, outer, i) == (i, outer)
    # no tile used at all (not the layer's layout: every expert owns one)
    assert tuple(int(v) for v in pk._gmm_x_map(
        0, 3, g, np.asarray([0], np.int32))) == (0, 0)


@pytest.mark.parametrize("act", moe.HIDDEN_ACTS)
def test_the_kernel_path_equals_ragged_dot_at_four_times_the_mean_share(
        monkeypatch, act):
    """A buffer 4 times the mean share, so most of its tiles hold nothing:
    the kernels' layer against XLA's ``ragged_dot`` layer, values, the three
    gradients and the counts both paths share; the kernel path also counts
    its tiles."""
    from mxnet_tpu.ops import pallas_kernels as pk

    make = _gated_weights if act == "silu" else _weights
    w = make(tokens=96, width=128, hidden=40, experts=16, seed=17)
    w["router"] = 0.4 * w["router"]
    held = (2, 3, 9, 12)
    ids = jnp.asarray(held)

    def layer(x, up, down):
        return moe.held_experts_layer(
            x, w["router"], w["bias"], up[ids], down[ids], held=held, k=K,
            scaling=SCALING, capacity_factor=4.0, hidden_act=act)

    def loss(x, up, down):
        return jnp.sum(jnp.sin(layer(x, up, down)[0]))

    args = (w["x"], w["up"], w["down"])
    ragged, ragged_stats = layer(*args)
    ragged_grads = jax.grad(loss, argnums=(0, 1, 2))(*args)
    monkeypatch.setattr(pk, "GROUP_TILE", 8)
    monkeypatch.setattr(moe, "_grouped_platform", lambda: "tpu")
    out, stats = layer(*args)
    np.testing.assert_allclose(out, ragged, atol=1e-4)
    for got, want in zip(jax.grad(loss, argnums=(0, 1, 2))(*args),
                         ragged_grads):
        np.testing.assert_allclose(got, want, atol=2e-3)
    shared = len(moe.HELD_STATS) - 2
    np.testing.assert_array_equal(stats[:shared], ragged_stats[:shared])
    assert list(ragged_stats[shared:]) == [0, 0]          # no tiles there
    stats = dict(zip(moe.HELD_STATS, np.asarray(stats)))
    # 4 x 96 x 3 x 4/16 = 288 rows up to 512, and a tile a held expert
    assert stats["tiles"] == 512 // 8 + 4
    assert stats["rows_held"] / 8 <= stats["tiles_used"] \
        <= stats["rows_held"] / 8 + 4 < stats["tiles"] / 2


# -- the block: counts on the device, read when somebody asks -----------------
def _block(held=(0, 1), experts=8):
    block = nh.NemotronHMoE(16, experts, 2, 24, 32,
                            routed_scaling_factor=SCALING, held=held)
    block.initialize()
    return block


def test_block_counts_accumulate_in_training_and_reach_the_gauges():
    block = _block()
    x = mx.nd.array(np.random.default_rng(0).standard_normal((2, 10, 16)))
    base = mx.telemetry.snapshot()
    block(x)                                        # predict mode: no counts
    assert mx.telemetry.snapshot()["moe.steps"] == base["moe.steps"]
    with mx.autograd.train_mode():
        block(x)
        block(x)
    snap = mx.telemetry.snapshot()
    assert snap["moe.steps"] - base["moe.steps"] == 2
    assert snap["moe.rows_routed"] - base["moe.rows_routed"] == 2 * 20 * 2
    counts = block.counts.data().asnumpy()
    assert counts[moe.HELD_STATS.index("steps")] == 2
    assert counts[moe.HELD_STATS.index("rows_held")] \
        == snap["moe.rows_held"] - base["moe.rows_held"]
    assert snap["moe.rows_held_share"] > 0
    assert snap["moe.load_max_over_mean"] > 0


@pytest.fixture
def gauges_as_found():
    """The ``moe.*`` gauges sum over every expert layer the process built,
    for ever: a test that overflows on purpose takes its layers' counts out
    again, so that a later test of the same worker (a benchmark rehearsal
    that asserts ``moe.rows_overflow`` 0) reads what its own run counted."""
    from mxnet_tpu.gluon.model_zoo import sparse_experts

    found = len(sparse_experts._LAYERS)
    yield
    del sparse_experts._LAYERS[found:]


def test_block_overflow_becomes_a_fallback_event_when_events_are_read(
        gauges_as_found):
    # every token chooses expert 0, the one held: 400 rows for a buffer of
    # twice the mean share (400 x 2 x 1/8), 256 rows
    block = _block(held=(0,))
    block.e_score_correction_bias.set_data(
        mx.nd.array(np.array([10.0] + [0.0] * 7, np.float32)))
    x = mx.nd.array(np.random.default_rng(1).standard_normal((1, 400, 16)))
    seq = max((e["seq"] for e in mx.telemetry.events("fallback")), default=0)
    with mx.autograd.train_mode():
        block(x)
    new = [e for e in mx.telemetry.events("fallback") if e["seq"] > seq]
    assert [e["name"] for e in new] == ["moe.rows_overflow"]
    assert new[0]["rows"] > 0
    # reading again reports nothing twice
    assert not [e for e in mx.telemetry.events("fallback")
                if e["seq"] > new[0]["seq"]]
    assert mx.telemetry.snapshot()["moe.rows_overflow"] >= new[0]["rows"]


def test_tiles_used_share_is_what_the_tile_arithmetic_gives(monkeypatch):
    """Eight held experts of which two receive every row: 20 tokens, two
    experts a token, tiles of 8 rows: 3 tiles each for the two, one each
    for the six that own a tile and fill none, of a buffer of 256 rows and
    a tile an expert: 12 of 40."""
    from mxnet_tpu.gluon.model_zoo import sparse_experts as se
    from mxnet_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "GROUP_TILE", 8)
    monkeypatch.setattr(moe, "_grouped_platform", lambda: "tpu")
    monkeypatch.setattr(se, "_LAYERS", [])          # this layer's alone
    assert mx.telemetry.snapshot()["moe.tiles_used_share"] is None
    block = nh.NemotronHMoE(128, 8, 2, 24, 32, routed_scaling_factor=SCALING)
    block.initialize()
    block.e_score_correction_bias.set_data(
        mx.nd.array(np.array([10.0, 10.0] + [0.0] * 6, np.float32)))
    x = mx.nd.array(np.random.default_rng(4).standard_normal((1, 20, 128)))
    with mx.autograd.train_mode():
        block(x)
        block(x)
    counts = dict(zip(moe.HELD_STATS, block.counts.data().asnumpy()))
    assert counts["rows_held"] == 2 * 40 and counts["load_max"] == 20
    assert counts["tiles_used"] == 2 * 12 and counts["tiles"] == 2 * 40
    assert mx.telemetry.snapshot()["moe.tiles_used_share"] == 12 / 40


def test_routed_part_and_shared_expert_are_separate():
    block = _block()
    x = mx.nd.array(np.random.default_rng(2).standard_normal((1, 6, 16)))
    np.testing.assert_allclose(
        block(x).asnumpy(),
        (block.routed(x) + block.shared_expert(x)).asnumpy(), atol=1e-6)


# -- the gated expert: w_down (silu(w_gate x) * (w_up x)) ---------------------
def _gated_weights(**kw):
    """``up`` holds gate and up side by side: (experts, width, 2 x hidden)."""
    w = _weights(**kw)
    gate = 0.2 * jax.random.normal(jax.random.PRNGKey(99), w["up"].shape)
    return {**w, "up": jnp.concatenate([gate, w["up"]], axis=-1)}


def _gated_loop(w, held, k=K):
    s = jax.nn.sigmoid(jnp.einsum("tm,em->te", w["x"], w["router"],
                                  precision="highest"))
    _, chosen = jax.lax.top_k(s + w["bias"], k)
    picked = jnp.take_along_axis(s, chosen, -1)
    weight = picked / picked.sum(-1, keepdims=True) * SCALING
    hidden = w["down"].shape[1]
    out = 0.0
    for e in held:
        mine = jnp.sum(jnp.where(chosen == e, weight, 0.0), -1)
        gate, up = w["up"][e][:, :hidden], w["up"][e][:, hidden:]
        h = jax.nn.silu(w["x"] @ gate) * (w["x"] @ up)
        out = out + mine[:, None] * (h @ w["down"][e])
    return out


def _gated(w, held, k=K):
    ids = jnp.asarray(held)
    return moe.held_experts_layer(
        w["x"], w["router"], w["bias"], w["up"][ids], w["down"][ids],
        held=held, k=k, scaling=SCALING, capacity_factor=4.0,
        hidden_act="silu")


@pytest.mark.parametrize("kernel", [False, True])
def test_the_gated_expert_equals_the_loop_over_experts(monkeypatch, kernel):
    """Values and gradients, through XLA's ragged product and through the
    layout a TPU trace takes (Pallas interpreter; a hidden width of 40 is
    padded to whole lanes, gate and up each alone)."""
    from mxnet_tpu.ops import pallas_kernels as pk

    if kernel:
        monkeypatch.setattr(pk, "GROUP_TILE", 8)
        monkeypatch.setattr(moe, "_grouped_platform", lambda: "tpu")
    w = _gated_weights(tokens=96, width=128, hidden=40, experts=16, seed=11)
    w["router"] = 0.4 * w["router"]
    held = (0, 3, 5, 6)
    out, stats = _gated(w, held)
    np.testing.assert_allclose(out, _gated_loop(w, held), atol=1e-4)
    assert float(stats[moe.HELD_STATS.index("rows_overflow")]) == 0

    def ours(x, up, down):
        return jnp.sum(jnp.sin(_gated({**w, "x": x, "up": up, "down": down},
                                      held)[0]))

    def loop(x, up, down):
        return jnp.sum(jnp.sin(_gated_loop(
            {**w, "x": x, "up": up, "down": down}, held)))

    args = (w["x"], w["up"], w["down"])
    for got, want in zip(jax.grad(ours, argnums=(0, 1, 2))(*args),
                         jax.grad(loop, argnums=(0, 1, 2))(*args)):
        np.testing.assert_allclose(got, want, atol=2e-3)


def test_the_gated_shares_add_up_to_the_uncut_layer():
    """Eight ranks, each told its 8 of 64 experts, four a token."""
    w = _gated_weights(tokens=48, experts=64, seed=3)
    whole = _gated_loop(w, range(64), k=4)
    total = sum(_gated(w, tuple(range(8 * r, 8 * r + 8)), k=4)[0]
                for r in range(8))
    np.testing.assert_allclose(total, whole, atol=2e-5)


def test_the_relu2_expert_is_unchanged_to_the_bit():
    """``hidden_act`` left out is ``relu2``, and the ``relu2`` layer is the
    expression it was before the layer knew a second form: XLA's two ragged
    products over the sorted rows with ``relu(.)^2`` between them, bit for
    bit (the rows' order is the counting sort's, so the old expression is
    rebuilt from the layer's own statistics-free inputs)."""
    w = _weights(tokens=64, seed=13)
    held = (1, 2, 5, 8)
    ids = jnp.asarray(held)
    args = (w["x"], w["router"], w["bias"], w["up"][ids], w["down"][ids])
    kw = dict(held=held, k=K, scaling=SCALING, capacity_factor=4.0)
    default, _ = moe.held_experts_layer(*args, **kw)
    named, _ = moe.held_experts_layer(*args, hidden_act="relu2", **kw)
    np.testing.assert_array_equal(default, named)

    # the expression as PR 31 wrote it, on the same sorted rows
    s = jax.nn.sigmoid(jnp.einsum("tm,em->te", w["x"], w["router"],
                                  precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(s + w["bias"], K)
    picked = jnp.take_along_axis(s, chosen, -1)
    weight = (picked / picked.sum(-1, keepdims=True) * SCALING).reshape(-1)
    flat, token = chosen.reshape(-1), jnp.arange(64 * K) // K
    order = jnp.concatenate([jnp.nonzero(flat == e)[0] for e in held])
    sizes = jnp.asarray([int(jnp.sum(flat == e)) for e in held], jnp.int32)
    rows = moe.held_buffer_rows(64, K, 16, len(held), 4.0)
    pad = rows - order.shape[0]
    gathered = jnp.pad(w["x"][token[order]], ((0, pad), (0, 0)))
    h = jax.lax.ragged_dot(gathered, w["up"][ids], sizes)
    h = jnp.square(jax.nn.relu(h))
    y = jax.lax.ragged_dot(h, w["down"][ids], sizes)
    y = y * jnp.pad(weight[order], (0, pad))[:, None]
    want = jnp.zeros_like(w["x"]).at[
        jnp.pad(token[order], (0, pad))].add(y)
    np.testing.assert_array_equal(default, want)


def test_an_unknown_hidden_act_and_a_wrong_width_are_refused():
    w = _weights()
    ids = jnp.arange(4)
    args = (w["x"], w["router"], w["bias"], w["up"][ids], w["down"][ids])
    with pytest.raises(ValueError, match="hidden_act="):
        moe.held_experts_layer(*args, held=(0, 1, 2, 3), k=K,
                               hidden_act="gelu")
    with pytest.raises(ValueError, match="w_up"):       # no gate beside up
        moe.held_experts_layer(*args, held=(0, 1, 2, 3), k=K,
                               hidden_act="silu")
