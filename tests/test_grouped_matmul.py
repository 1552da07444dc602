"""The routed experts' grouped matrix product and the plan that feeds
it: ``kernels/grouped_matmul.py`` (interpreted) against its jnp oracle,
and ``nn/moe_dropless.dispatch_plan``'s layout."""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.grouped_matmul import (column_tile, grouped_matmul,
                                               grouped_matmul_reference)
from paddle_tpu.nn.moe_dropless import (buffer_rows, dispatch_plan,
                                        dropless_experts, route_sigmoid)

TM = 8


@pytest.mark.parametrize("live", [0, 1, 3, 6], ids=lambda n: f"live{n}")
@pytest.mark.parametrize("k,n", [(32, 48), (16, 256)])
def test_kernel_equals_oracle_and_zeroes_dead_tiles(k, n, live):
    """Six row tiles over three groups; the tiles past ``live`` write
    zeros whatever their rows hold (here NaN)."""
    rng = np.random.default_rng(k + live)
    x = rng.standard_normal((6 * TM, k)).astype(np.float32)
    x[live * TM:] = np.nan
    w = jnp.asarray(rng.standard_normal((3, k, n)), jnp.float32)
    tile_group = jnp.asarray([0, 0, 1, 2, 2, 2], jnp.int32)
    out = np.asarray(grouped_matmul(jnp.asarray(x), w, tile_group, live,
                                    tm=TM, interpret=True))
    assert np.isfinite(out).all()
    assert not out[live * TM:].any()
    ref = np.asarray(grouped_matmul_reference(
        jnp.asarray(np.nan_to_num(x)), w, tile_group, live, tm=TM))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_column_tile_keeps_a_weight_block_within_six_mebibytes():
    assert column_tile(6144, 2048, 2) == 512      # K-EXAONE's up / gate
    assert column_tile(2048, 6144, 2) == 1536     # its down projection
    assert column_tile(64, 32, 4) == 32           # no 128-multiple: whole
    assert column_tile(64, 384, 4) == 384


def test_kernel_refuses_rows_that_are_not_whole_tiles():
    with pytest.raises(ValueError, match="whole tiles"):
        grouped_matmul(jnp.zeros((12, 8)), jnp.zeros((2, 8, 8)),
                       jnp.zeros((2,), jnp.int32), 1, tm=TM)
    with pytest.raises(ValueError, match="has K"):
        grouped_matmul(jnp.zeros((16, 8)), jnp.zeros((2, 4, 8)),
                       jnp.zeros((2,), jnp.int32), 1, tm=TM)


def _plan(idx, live, first, held):
    p = dispatch_plan(jnp.asarray(idx, jnp.int32), jnp.asarray(live),
                      first=first, held=held, tm=TM)
    return {k: np.asarray(v) for k, v in p.items()}


def test_plan_lays_each_experts_pairs_at_a_tile_start():
    rng = np.random.default_rng(1)
    t, k, first, held = 37, 3, 4, 5
    idx = np.stack([rng.permutation(12)[:k] for _ in range(t)])
    live = rng.random(t) > 0.2
    p = _plan(idx, live, first, held)
    m = buffer_rows(t, k, held, TM)
    assert p["src"].shape == (m,) and p["tile_group"].shape == (m // TM,)
    here = (idx >= first) & (idx < first + held) & live[:, None]
    assert (p["here"] == here).all()
    counts = np.bincount((idx - first)[here], minlength=held)
    assert (p["counts"] == counts).all()
    starts = np.cumsum(-(-counts // TM) * TM) - (-(-counts // TM) * TM)
    assert p["live_tiles"] == (-(-counts // TM)).sum()
    seen = set()
    for tok, j in zip(*np.nonzero(here)):
        e, row = idx[tok, j] - first, p["pair_row"][tok, j]
        assert starts[e] <= row < starts[e] + counts[e]    # its group's rows
        assert p["src"][row] == tok                        # reads its token
        assert p["tile_group"][row // TM] == e
        assert row not in seen                             # one pair a row
        seen.add(row)
    assert len(seen) == here.sum()


def test_worst_routing_fits_the_buffer():
    """Every token sends all its picks to held experts, and all to as few
    as it can: the buffer holds them, none is dropped."""
    t, k, held = 16, 4, 6
    idx = np.tile(np.arange(k), (t, 1))            # experts 0..3, all held
    p = _plan(idx, np.ones(t, bool), 0, held)
    assert p["counts"].tolist() == [t] * k + [0, 0]
    assert p["here"].all()
    assert len(set(p["pair_row"].reshape(-1).tolist())) == t * k
    assert p["pair_row"].max() < buffer_rows(t, k, held, TM)


def test_dead_slots_of_a_packed_step_route_nowhere():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((10, 16)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((16, 6)), jnp.float32)
    idx, gates = route_sigmoid(x, router, jnp.zeros((6,)), top_k=2,
                               scaling=2.5)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 2.5, rtol=1e-6)
    w = [jnp.asarray(rng.standard_normal(s), jnp.float32)
         for s in ((6, 16, 8), (6, 16, 8), (6, 8, 16))]
    live = jnp.asarray([True] * 6 + [False] * 4)
    y, stats = dropless_experts(x, idx, gates, live, *w, first=0, tm=TM,
                                interpret=True)
    y = np.asarray(y)
    assert not y[6:].any() and np.abs(y[:6]).min() > 0
    assert int(stats[0]) == 12                     # six live tokens x two
    full, _ = dropless_experts(x, idx, gates, jnp.ones((10,), bool), *w,
                               first=0, tm=TM, interpret=True)
    np.testing.assert_allclose(y[:6], np.asarray(full)[:6], rtol=1e-6)
