"""Two page groups in one ``PagedKVPool``: full-attention layers keep a
row's pages for its life, window layers hold a bounded set a row with
their own tables and free list. Host-side bookkeeping only (tiny device
arrays)."""
import jax.numpy as jnp
import pytest

from paddle_tpu.serving.kv_cache import (NULL_PAGE, InvariantViolation,
                                         PagedKVPool, PoolExhausted)
from paddle_tpu.serving.scheduler import (Scheduler, SchedulerConfig,
                                          Sequence, SequenceStatus)

PS, W, CHUNK = 4, 8, 16


def _pool(num_pages=512, window_pages=64, layers=5, window_layers=(0, 1, 2, 4)):
    return PagedKVPool(layers, 2, 8, num_pages=num_pages, page_size=PS,
                       dtype=jnp.float32, window_layers=window_layers,
                       window=W, window_pages=window_pages)


def _grow(pool, sid, total, chunk):
    """A row's life as the scheduler drives it: chunks, then decode."""
    held = []
    n = 0
    while n < total:
        step = min(chunk, total - 8 - n) if n < total - 8 else 1
        pool.prepare_append(sid, n + step)
        n += step
        held.append(sum(p != NULL_PAGE for p in pool.window_block_table(sid)))
        pool.check_invariants()
    return held


def test_layers_of_each_group_have_their_own_page_count():
    pool = _pool(num_pages=100, window_pages=20)
    assert [k.shape[1] for k, _ in pool.kv] == [20, 20, 20, 100, 20]
    assert pool.capacity == 99 and pool.window_capacity == 19
    per_layer = 2 * 2 * PS * 8 * 4
    assert pool.page_bytes == per_layer            # one full layer
    assert pool.window_page_bytes == 4 * per_layer
    assert pool.pool_bytes == 100 * per_layer + 20 * 4 * per_layer


@pytest.mark.parametrize("chunk", [CHUNK, 3], ids=["chunk_gt_window",
                                                   "chunk_lt_window"])
def test_long_row_holds_bounded_window_pages_and_growing_full_pages(chunk):
    pool = _pool()
    total = 40 * W
    pool.allocate("a", 0)
    held = _grow(pool, "a", total, chunk)
    bound = pool.window_row_bound(chunk)
    assert bound == -(-(W - 1 + chunk) // PS) + 1
    assert max(held) <= bound
    assert len(pool.block_table("a")) == total // PS       # grew with it
    assert pool.used_pages == total // PS
    assert pool.window_pages_used == held[-1] <= bound
    # the slots under the window name the null page; the window's are mapped
    wt = pool.padded_window_table("a", 100)
    first = (total - W + 1) // PS       # of the next token's window
    assert all(p == NULL_PAGE for p in wt[:first - 1])    # of the last one's
    assert all(p != NULL_PAGE for p in wt[first:total // PS])
    pool.free("a")
    pool.check_invariants()
    assert pool.free_pages == pool.capacity
    assert len(pool._wfree) == pool.window_capacity


def test_window_group_exhaustion_is_all_or_nothing():
    pool = _pool(window_pages=6)               # five allocatable
    pool.allocate("a", 0)
    pool.prepare_append("a", 12)               # three window pages
    pool.allocate("b", 0)
    free_full = pool.free_pages
    with pytest.raises(PoolExhausted, match="window group"):
        pool.prepare_append("b", 12)           # needs three, two free
    assert pool.free_pages == free_full        # the full group claimed none
    assert pool.seq_len("b") == 0
    pool.check_invariants()
    assert not pool.window_can_hold(12) and pool.window_can_hold(8)
    assert not pool.can_allocate(12)
    pool.free("a")
    pool.prepare_append("b", 12)
    pool.check_invariants()


def test_sharing_and_rollback_under_the_window_are_refused_by_name():
    pool = _pool()
    pool.allocate("a", 0)
    pool.prepare_append("a", 32)
    pool.prepare_append("a", 33)
    for call in (lambda: pool.fork("b", "a"),
                 lambda: pool.pin("c", "a", 32),
                 lambda: pool.export_pages("a"),
                 lambda: pool.adopt_sequence("b", 4, []),
                 lambda: pool.fork_pinned("b", "c", 4)):
        with pytest.raises(ValueError, match="window layers"):
            call()
    pool.rollback("a", 32)                     # its window is still held
    with pytest.raises(ValueError, match="released"):
        pool.rollback("a", 8)
    with pytest.raises(ValueError, match="window >= 1"):
        PagedKVPool(2, 2, 8, num_pages=8, page_size=PS, window_layers=(0,))
    with pytest.raises(ValueError, match="unquantized"):
        PagedKVPool(2, 2, 8, num_pages=8, page_size=PS, dtype=jnp.int8,
                    window_layers=(0,), window=W, window_pages=8)


def test_invariants_catch_a_hole_inside_the_window():
    pool = _pool()
    pool.allocate("a", 0)
    pool.prepare_append("a", 20)
    wt = pool._wtables["a"]
    pool._wfree.append(wt[-1])
    wt[-1] = NULL_PAGE
    with pytest.raises(InvariantViolation, match="inside the window"):
        pool.check_invariants()


def _sched(pool, rows=4):
    return Scheduler(pool, SchedulerConfig(max_num_seqs=rows,
                                           chunk_size=CHUNK, q_block=4),
                     max_pages_per_seq=128)


def _seq(i, prompt, new):
    return Sequence(seq_id=f"s{i}", prompt_ids=list(range(prompt)),
                    max_new_tokens=new, arrival=float(i))


def _drive(sched, steps):
    for _ in range(steps):
        sched.admit()
        plan = sched.prepare_step()
        sched.pool.check_invariants()
        if plan is None:
            continue
        for seq, _, q_len in plan.rows:
            seq.cached_len += q_len
            if seq.cached_len == seq.total_len:
                seq.tokens.append(0)
                if len(seq.tokens) >= seq.max_new_tokens:
                    sched.finish(seq)


def test_preemption_recompute_cancel_finish_keep_both_groups_whole():
    # full group: 47 pages for three rows that want 25 each, so the
    # scheduler preempts; window group: room for every row's bound
    pool = _pool(num_pages=48, window_pages=4 * 7 + 1)
    sched = _sched(pool)
    seqs = [_seq(i, 60, 40) for i in range(3)]
    for s in seqs:
        sched.add(s)
    preempted = 0
    for _ in range(400):
        sched.admit()
        plan = sched.prepare_step()
        preempted += len(sched.last_preempted)
        pool.check_invariants()
        if plan is None:
            break
        for seq, _, q_len in plan.rows:
            seq.cached_len += q_len
            if seq.cached_len == seq.total_len:
                seq.tokens.append(0)
                if len(seq.tokens) >= seq.max_new_tokens:
                    sched.finish(seq)
        if seqs[2].status is SequenceStatus.RUNNING and seqs[2].tokens:
            sched.finish(seqs[2], SequenceStatus.CANCELLED)   # a cancel
    assert preempted > 0
    assert all(s.status in (SequenceStatus.FINISHED,
                            SequenceStatus.CANCELLED) for s in seqs)
    pool.check_invariants()
    assert pool.free_pages == pool.capacity
    assert len(pool._wfree) == pool.window_capacity


def test_admission_counts_the_window_group():
    # the full group could take all four rows; the window group has room
    # for two first chunks (4 pages each)
    pool = _pool(num_pages=512, window_pages=8 + 1)
    sched = _sched(pool)
    for i in range(4):
        sched.add(_seq(i, 64, 4))
    assert len(sched.admit()) == 2
    assert len(sched.waiting) == 2
    _drive(sched, 200)
    assert not sched.has_unfinished()
    assert pool.free_pages == pool.capacity
    assert len(pool._wfree) == pool.window_capacity


def test_pool_without_window_layers_is_what_it_was():
    pool = PagedKVPool(2, 2, 8, num_pages=16, page_size=PS)
    assert pool.window_layers == () and pool.window_capacity == 0
    assert pool.window_pages_used == 0 and pool.window_row_bound(16) == 0
    assert pool.window_can_hold(10 ** 6)
    pool.allocate("a", 10)
    pool.fork("b", "a", 8)
    pool.prepare_append("b", 11)
    pool.check_invariants()
    assert pool.page_bytes == 2 * 2 * 2 * PS * 8 * 4
