"""``kernels/paged_attention.py::kv_append`` against the expression it
replaced (``P.reshape(Hkv, N * ps, d).at[:, slot].set(x)``, kept here as
the reference): every page but the null page bit-equal, the null page
left as it was (dead tokens are dropped, where the scatter wrote them to
it), over random tables with dead slots, chunks that straddle pages,
decode rows at a page's edges, kv heads 4 / 8, head widths 64 / 128 and a
pool whose page count is no multiple of 8; the whole fp layer's pools
after one step bit-equal with the reference swapped in; and under a
2-device model-axis mesh no collective as large as a pool."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.paged_attention import kv_append
from paddle_tpu.serving.kv_cache import NULL_PAGE

PS = 16


def scatter_append(P, slot, x, interpret=None):
    """What every append site held before ``kv_append``."""
    hkv, n, ps, d = P.shape
    return P.reshape(hkv, n * ps, d).at[:, slot].set(x.astype(P.dtype)) \
        .reshape(hkv, n, ps, d)


def _slots(rows, budget, q_block, pages, rng, ps=PS):
    """Pack ``rows`` of ``(first_position, q_len)`` into ``budget`` token
    slots, each row from a multiple of ``q_block``, pages drawn without
    replacement from 1..pages-1. Returns the ``[budget]`` slot vector
    (dead slots on the null page, at position 0's offset)."""
    free = list(rng.permutation(np.arange(1, pages)))
    slot = np.full(budget, NULL_PAGE * ps, np.int32)
    cursor = 0
    for pos0, q_len in rows:
        first = pos0 // ps        # only the pages written need a name
        table = [free.pop()
                 for _ in range(first, (pos0 + q_len - 1) // ps + 1)]
        for i in range(q_len):
            p = pos0 + i
            slot[cursor + i] = table[p // ps - first] * ps + p % ps
        cursor += -(-q_len // q_block) * q_block
    assert cursor <= budget
    return slot


def _check(hkv, pages, d, rows, budget, q_block=8, dtype=jnp.bfloat16,
           seed=0, ps=PS):
    rng = np.random.default_rng(seed)
    P = jnp.asarray(rng.standard_normal((hkv, pages, ps, d)), dtype)
    x = jnp.asarray(rng.standard_normal((hkv, budget, d)), dtype)
    slot = _slots(rows, budget, q_block, pages, rng, ps)
    want = np.asarray(scatter_append(P, jnp.asarray(slot), x)
                      .astype(jnp.float32))
    got = np.asarray(kv_append(P, jnp.asarray(slot), x, interpret=True)
                     .astype(jnp.float32))
    np.testing.assert_array_equal(got[:, 1:], want[:, 1:])
    np.testing.assert_array_equal(
        got[:, NULL_PAGE], np.asarray(P[:, NULL_PAGE].astype(jnp.float32)))
    live = slot[slot >= ps]
    assert len(live) == sum(q for _, q in rows)
    return slot


# (first position, q_len) a row
TRAFFIC = {
    # decode rows and a chunk, most slots dead
    "mixed": ([(200, 1), (17, 1), (64, 40), (5, 1)], 96),
    # every slot dead: nothing is written at all
    "all_dead": ([], 32),
    # dead slots between rows write the null page's row 0 many times over
    # in the reference; here they are dropped
    "null_page_duplicates": ([(3, 1), (4, 1), (300, 1)], 64),
    # a chunk from mid-page over two page boundaries
    "chunk_straddles_pages": ([(10, 30)], 32),
    # a chunk of exactly one page, and one that ends on a page's last slot
    "chunk_whole_page": ([(32, 16), (8, 8)], 32),
    # a decode row on a page's first and on its last slot
    "decode_first_slot": ([(48, 1), (0, 1)], 16),
    "decode_last_slot": ([(47, 1), (15, 1)], 16),
    # more runs than pages in flight (8): the buffers go round twice
    "many_rows": ([(16 * i + i, 1) for i in range(20)], 160),
    # a budget that is no multiple of 16, rows from multiples of 1
    "q_block_1": ([(7, 1), (31, 1), (32, 1), (100, 1), (9, 1)], 5),
}


@pytest.mark.parametrize("name", list(TRAFFIC))
def test_kv_append_matches_the_scatter(name):
    rows, budget = TRAFFIC[name]
    _check(8, 64, 128, rows, budget,
           q_block=1 if name == "q_block_1" else 8)


@pytest.mark.parametrize("hkv", [4, 8])
@pytest.mark.parametrize("d", [64, 128])
def test_kv_append_heads_and_widths(hkv, d):
    rng = np.random.default_rng(hkv * d)
    rows = [(int(rng.integers(0, 300)), int(q)) for q in (1, 1, 24, 1, 9)]
    _check(hkv, 128, d, rows, 64, seed=hkv + d)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_kv_append_window_group_pool(dtype):
    """The window group's pool: a page count that is no multiple of 8."""
    _check(8, 27, 128, [(120, 20), (250, 1), (3, 1)], 40,
           dtype=jnp.dtype(dtype))


@pytest.mark.parametrize("page_size", [8, 32])
def test_kv_append_other_page_sizes(page_size):
    _check(4, 40, 128, [(page_size - 3, 2 * page_size + 5), (70, 1)], 96,
           ps=page_size)


@pytest.mark.parametrize("kind", ["llama", "window_qk_norm"])
def test_fp_layer_pools_bit_equal_after_one_step(kind, monkeypatch):
    """``_ragged_fp_layer`` with ``kv_append`` against the same layer with
    the old scatter swapped in: hidden state and both pools (off the null
    page) bit-equal."""
    from types import SimpleNamespace

    from paddle_tpu.models.generation import LayerKind
    from paddle_tpu.serving import spec_decode as sd

    rng = np.random.default_rng(3)
    hid, H, hkv, d, ffn, T, R, pps, pages = 64, 4, 2, 32, 96, 32, 4, 8, 24
    cfg = SimpleNamespace(num_attention_heads=H, num_key_value_heads=hkv,
                          head_dim=d, rms_norm_eps=1e-5, rope_theta=1e4)

    def w(*shape):
        return jnp.asarray(rng.standard_normal(shape) * 0.05, jnp.float32)
    lyr = {"ln1": 1 + w(hid), "ln2": 1 + w(hid), "q": w(hid, H * d),
           "k": w(hid, hkv * d), "v": w(hid, hkv * d), "o": w(H * d, hid),
           "gate": w(hid, ffn), "up": w(hid, ffn), "down": w(ffn, hid)}
    layer_kind = LayerKind()
    if kind == "window_qk_norm":
        lyr.update(q_norm=1 + w(d), k_norm=1 + w(d))
        layer_kind = LayerKind(window=24, qk_norm=True)
    # row 0: a 12-token chunk from position 10; row 1: decode at 31
    q_starts = np.array([0, 16, T, T], np.int32)
    q_lens = np.array([12, 1, 0, 0], np.int32)
    kv_lens = np.array([22, 32, 0, 0], np.int32)
    tbls = np.zeros((R, pps), np.int32)
    tbls[0, :2], tbls[1, :2] = [5, 9], [3, 17]
    positions = np.zeros(T, np.int32)
    positions[:12] = 10 + np.arange(12)
    positions[16] = 31
    tok_row, live = sd._ragged_packing(jnp.asarray(q_starts),
                                       jnp.asarray(q_lens), T)
    h = w(1, T, hid) * 20
    Kp, Vp = (jnp.asarray(rng.standard_normal((hkv, pages, PS, d)),
                          jnp.float32) for _ in range(2))

    def run():
        return sd._ragged_fp_layer(
            lyr, h, Kp, Vp, jnp.asarray(positions), jnp.asarray(tbls),
            tok_row, live, jnp.asarray(q_starts),
            jnp.asarray(q_lens), jnp.asarray(kv_lens), cfg, PS, pps, 8,
            True, kind=layer_kind)

    got = run()
    monkeypatch.setattr(sd, "kv_append", scatter_append)
    want = run()
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    for g, w_ in zip(got[1:], want[1:]):
        np.testing.assert_array_equal(np.asarray(g)[:, 1:],
                                      np.asarray(w_)[:, 1:])


def test_kv_append_under_a_model_mesh_gathers_no_pool():
    """A pool sharded over its kv-head axis stays where it is: the
    partitioned append holds no collective as large as a device's share
    of the pool, and each device's heads come out as on one device."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P_

    devices = jax.devices()
    if len(devices) < 2:
        pytest.skip("needs two devices")
    mesh = Mesh(np.array(devices[:2]), ("model",))
    heads = NamedSharding(mesh, P_("model"))
    rng = np.random.default_rng(1)
    hkv, pages, d, T = 4, 32, 128, 32
    P = jnp.asarray(rng.standard_normal((hkv, pages, PS, d)), jnp.bfloat16)
    x = jnp.asarray(rng.standard_normal((hkv, T, d)), jnp.bfloat16)
    slot = jnp.asarray(_slots([(10, 20), (40, 1)], T, 8, pages, rng))
    want = np.asarray(kv_append(P, slot, x, interpret=True)
                      .astype(jnp.float32))

    fn = jax.jit(lambda P, s, x: kv_append(P, s, x, interpret=True),
                 donate_argnums=0, out_shardings=heads)
    args = (jax.device_put(P, heads), slot, jax.device_put(x, heads))
    text = fn.lower(*args).compile().as_text()
    share = hkv * pages * PS * d // 2
    for line in text.splitlines():
        m = re.search(r"=\s*\w+\[([\d,]+)\]\S*\s+"
                      r"(all-gather|all-reduce|all-to-all|"
                      r"collective-permute)", line)
        if m:
            n = int(np.prod([int(s) for s in m.group(1).split(",")]))
            assert n < share, f"a pool-sized collective: {line.strip()[:160]}"
    got = fn(*args)
    assert got.sharding.is_equivalent_to(heads, got.ndim)
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)), want)
