"""Explicit pipeline-parallel schedules: GPipe (F-then-B), true 1F1B,
zero-bubble ZBH1, interleaved virtual-pipeline (vpp>1), and ZBV.

Reference: python/paddle/distributed/passes/pipeline_scheduler_pass/
pipeline_1f1b.py:45, pipeline_zero_bubble.py:61 and the VPP variant
pipeline_vpp.py build per-rank Job lists (F/B/W sub-programs) executed by
the multi-Job Plan executor (paddle/fluid/framework/new_executor/
interpreter/plan.h). The TPU-native rebuild keeps that structure but
compiles it into ONE program: ``build_schedule`` is a greedy
dependency-driven list scheduler over VIRTUAL stages (physical stage s,
chunk c) emitting static [tick, stage] tables (op / microbatch / chunk),
and ``pipeline_train_step`` executes the tables inside ``shard_map`` over
the ``pp`` mesh axis — each tick is a ``lax.switch`` on the device's
opcode, and activations/cotangents hop between neighbor stages with
``lax.ppermute`` riding ICI (the p2p of pp_utils/p2p_communication.py:573).

Virtual-stage layouts:
  interleaved (vpp>=1)  v = c*p + s   — chunk c of stage s is the
      (c*p+s)-th group of layers; activations always hop +1 on the ring
      (the reference's VPP layout, pp_layers.py get_stage_from_index).
  zbv (vpp==2)          v = s for the down chunk, v = 2p-1-s for the up
      chunk — the "V" shape of the zero-bubble-vertical schedule: chunk 0
      flows 0→p-1, chunk 1 flows back p-1→0, so stage 0 holds both the
      first and the LAST virtual stage (loss is computed on stage 0).

Zero-bubble (ZBH1/ZBV) splits backward into B_INPUT (activation-gradient,
on the critical inter-stage path) and B_WEIGHT (weight-gradient, freely
deferrable), so cooldown bubbles are filled with deferred weight-gradient
work. The executor computes B_INPUT/B_WEIGHT as separate ``jax.vjp`` pulls
against the saved stage input, so the split is real, not cosmetic.

Tick accounting: every op (F, B_INPUT, B_WEIGHT) is one tick, so a full
backward costs two ticks — the classic F:B = 1:2 cost model the schedules
are derived under. ``Schedule.bubble_ticks()`` counts per-stage idle ticks.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

# opcodes (values are the lax.switch branch indices)
IDLE, F_OP, BI_OP, W_OP = 0, 1, 2, 3
_OP_NAMES = {IDLE: "-", F_OP: "F", BI_OP: "Bi", W_OP: "Bw"}

# ring directions for the routing tables
_DIR_NONE, _DIR_PLUS, _DIR_MINUS, _DIR_LOCAL = 0, 1, 2, 3
_KIND_ACT, _KIND_COT = 0, 1


def _vmap_factory(kind: str, p: int, vpp: int):
    """(v_of(s, c), phys(v)) for the schedule's virtual-stage layout."""
    if kind == "zbv":
        def v_of(s, c):
            return s if c == 0 else 2 * p - 1 - s

        def phys(v):
            return (v, 0) if v < p else (2 * p - 1 - v, 1)
    else:
        def v_of(s, c):
            return c * p + s

        def phys(v):
            return (v % p, v // p)
    return v_of, phys


@dataclass
class Schedule:
    """A static pipeline schedule: [n_ticks, p] tables over virtual stages."""

    kind: str
    n_micro: int
    n_stages: int
    cap: int                 # max in-flight microbatches per physical stage
    op_table: np.ndarray     # int32 [T, p]
    micro_table: np.ndarray  # int32 [T, p]
    vpp: int = 1
    chunk_table: np.ndarray | None = field(default=None)

    def __post_init__(self):
        if self.chunk_table is None:
            self.chunk_table = np.zeros_like(self.op_table)

    @property
    def n_ticks(self) -> int:
        return int(self.op_table.shape[0])

    @property
    def n_virtual(self) -> int:
        return self.n_stages * self.vpp

    def layout(self):
        return _vmap_factory(self.kind, self.n_stages, self.vpp)

    def bubble_ticks(self, stage=None):
        """Idle ticks per stage over the schedule's full span."""
        idle = (self.op_table == IDLE).sum(axis=0)
        return int(idle[stage]) if stage is not None else idle.tolist()

    def bubble_total(self) -> int:
        return int((self.op_table == IDLE).sum())

    def bubble_fraction(self) -> float:
        return self.bubble_total() / float(self.op_table.size)

    def forward_layout(self) -> np.ndarray:
        """Forward-fill tick layout [n_micro + p - 1, p] int32: entry
        (t, s) is the microbatch whose F runs on stage s at forward
        tick t (micro ``t - s``), or -1 (fill/drain bubble).

        This is the tick ordering the single-jit TrainStep pipeline
        loop executes: the schedule's F ops collapsed onto consecutive
        ticks. Verified against the schedule's own op tables — each
        stage must emit F for micros 0..m-1 in order, respecting the
        1-tick neighbor dependency — so the executor and the explicit
        shard_map schedules share ONE ordering source. Backward ticks
        are realized by autodiff transposing the scan (the reverse
        drain); the steady-state F/B interleave of true 1F1B is a
        latency property the chip-tier shard_map executor keeps.
        """
        if self.vpp != 1:
            raise ValueError(
                f"forward_layout needs a vpp=1 schedule, got vpp={self.vpp}")
        m, p = self.n_micro, self.n_stages
        f_at = np.full((m, p), -1, np.int64)
        for s in range(p):
            seq = [(int(self.micro_table[t, s]), t)
                   for t in range(self.n_ticks)
                   if int(self.op_table[t, s]) == F_OP]
            if [i for i, _ in seq] != list(range(m)):
                raise ValueError(
                    f"stage {s} F order {[i for i, _ in seq]} is not "
                    f"the in-order microbatch sweep 0..{m - 1}")
            for i, t in seq:
                f_at[i, s] = t
        for s in range(1, p):
            if not (f_at[:, s] >= f_at[:, s - 1] + 1).all():
                raise ValueError(
                    f"stage {s} runs F before stage {s - 1} finished "
                    f"(neighbor dependency violated)")
        table = np.full((m + p - 1, p), -1, np.int32)
        for t in range(m + p - 1):
            for s in range(p):
                if 0 <= t - s < m:
                    table[t, s] = t - s
        return table

    def draw(self) -> str:
        """ASCII pipeline diagram (stages as rows, ticks as columns)."""
        rows = []
        for s in range(self.n_stages):
            cells = []
            for t in range(self.n_ticks):
                op, i = self.op_table[t, s], self.micro_table[t, s]
                c = int(self.chunk_table[t, s])
                tag = f"{_OP_NAMES[int(op)]}{int(i) if op else ' '}"
                if op and self.vpp > 1:
                    tag += f".{c}"
                cells.append(tag)
            rows.append(f"s{s}: " + " ".join(f"{c:>6}" for c in cells))
        return "\n".join(rows)


def forward_bubble_fraction(n_micro: int, n_stages: int) -> float:
    """Analytic fill/drain bubble of the forward-fill layout:
    ``(p - 1) / (m + p - 1)`` — each stage is busy m of the m + p - 1
    ticks. Matches ``Schedule.forward_layout()`` exactly (the -1
    fraction of the table) and is the per-step overhead model the bench
    artifact records (docs/PERF.md section 20)."""
    m, p = int(n_micro), int(n_stages)
    if m < 1 or p < 1:
        raise ValueError(f"need n_micro >= 1, n_stages >= 1, got {m}, {p}")
    return (p - 1) / float(m + p - 1)


def build_schedule(kind: str, n_micro: int, n_stages: int,
                   cap: int | None = None, vpp: int = 1) -> Schedule:
    """Greedy dependency-driven list scheduler over virtual stages.

    Dependencies (1-tick neighbor-communication latency), v = virtual stage:
      F(i,v)  needs F(i,v-1) done a tick earlier, and a free activation slot
              on its physical stage (started F minus completed B_WEIGHT
              across all chunks < cap);
      Bi(i,v) needs F(i,v) and Bi(i,v+1) done a tick earlier;
      Bw(i,v) needs Bi(i,v) done a tick earlier (frees the slot).

    Policies:
      fthenb  — per-stage strict forwards then backwards (B = Bi+Bw back to
                back), the reference's FThenB job order.
      1f1b    — backward-priority with atomic B: classic 1F1B at vpp=1, the
                interleaved VPP schedule at vpp>1.
      zbh1    — backward-input priority, weight-gradient work deferred into
                idle ticks (zero-bubble-horizontal).
      zbv     — the same split on the V-shaped two-chunk layout
                (zero-bubble-vertical); forces vpp=2.
    """
    if kind not in ("fthenb", "1f1b", "zbh1", "zbv"):
        raise ValueError(f"unknown schedule kind {kind!r}")
    if kind == "zbv":
        if vpp not in (1, 2):
            raise ValueError("zbv is a two-chunk (vpp=2) schedule")
        vpp = 2
    m, p = n_micro, n_stages
    V = p * vpp
    v_of, phys = _vmap_factory(kind, p, vpp)
    if cap is None:
        cap = m * vpp if kind == "fthenb" else min(V, m * vpp)
    cap = max(1, min(cap, m * vpp))

    next_f = [0] * V
    next_bi = [0] * V
    next_w = [0] * V
    f_done = [[None] * m for _ in range(V)]
    bi_done = [[None] * m for _ in range(V)]
    inflight = [0] * p
    forced_w = [None] * p    # (v, i) whose Bw must run next tick (atomic B)
    ops = [[] for _ in range(p)]
    chunks_of = [[c for c in range(vpp)] for _ in range(p)]

    def f_ready(v, t, s):
        i = next_f[v]
        if i >= m or inflight[s] >= cap:
            return False
        return v == 0 or (f_done[v - 1][i] is not None
                          and f_done[v - 1][i] <= t - 1)

    def bi_ready(v, t):
        i = next_bi[v]
        if i >= m or f_done[v][i] is None or f_done[v][i] > t - 1:
            return False
        return v == V - 1 or (bi_done[v + 1][i] is not None
                              and bi_done[v + 1][i] <= t - 1)

    def w_ready(v, t):
        i = next_w[v]
        return (i < next_bi[v] and bi_done[v][i] is not None
                and bi_done[v][i] <= t - 1)

    t = 0
    while any(next_w[v] < m for v in range(V)):
        if t > 4 * (m * vpp + V) * 3 + 64:  # safety: must terminate
            raise RuntimeError(f"schedule {kind} did not converge")
        for s in range(p):
            vs = [v_of(s, c) for c in chunks_of[s]]
            act = (IDLE, 0, 0)
            if forced_w[s] is not None:
                v, i = forced_w[s]
                act = (W_OP, i, v)
                next_w[v] += 1
                inflight[s] -= 1
                forced_w[s] = None
            elif kind == "fthenb":
                # F runs ahead only within the current activation window
                # of each virtual stage (the per-window bound below gives
                # the GPipe flush pattern at small caps); among ready ops
                # the deepest virtual stage goes first so completed
                # windows drain before new ones open
                fs = [v for v in vs if f_ready(v, t, s)
                      and next_f[v] < min(m, (next_bi[v] // max(cap // vpp, 1)
                                              + 1) * max(cap // vpp, 1))]
                bis = [v for v in vs if bi_ready(v, t)]
                if fs:
                    v = max(fs)
                    i = next_f[v]
                    act = (F_OP, i, v)
                    f_done[v][i] = t
                    next_f[v] += 1
                    inflight[s] += 1
                elif bis:
                    v = max(bis)
                    i = next_bi[v]
                    act = (BI_OP, i, v)
                    bi_done[v][i] = t
                    next_bi[v] += 1
                    forced_w[s] = (v, i)
            elif kind == "1f1b":
                bis = [v for v in vs if bi_ready(v, t)]
                fs = [v for v in vs if f_ready(v, t, s)]
                if bis:
                    v = max(bis)   # drain the deepest virtual stage first
                    i = next_bi[v]
                    act = (BI_OP, i, v)
                    bi_done[v][i] = t
                    next_bi[v] += 1
                    forced_w[s] = (v, i)
                elif fs:
                    v = max(fs)
                    i = next_f[v]
                    act = (F_OP, i, v)
                    f_done[v][i] = t
                    next_f[v] += 1
                    inflight[s] += 1
            else:  # zbh1 / zbv: Bi > F > deferred Bw
                bis = [v for v in vs if bi_ready(v, t)]
                fs = [v for v in vs if f_ready(v, t, s)]
                ws = [v for v in vs if w_ready(v, t)]
                if bis:
                    v = max(bis)
                    i = next_bi[v]
                    act = (BI_OP, i, v)
                    bi_done[v][i] = t
                    next_bi[v] += 1
                elif fs:
                    v = max(fs)
                    i = next_f[v]
                    act = (F_OP, i, v)
                    f_done[v][i] = t
                    next_f[v] += 1
                    inflight[s] += 1
                elif ws:
                    v = min(ws)    # oldest deferred weight-grad work first
                    act = (W_OP, next_w[v], v)
                    next_w[v] += 1
                    inflight[s] -= 1
            ops[s].append(act)
        t += 1

    T = t
    op_table = np.zeros((T, p), np.int32)
    micro_table = np.zeros((T, p), np.int32)
    chunk_table = np.zeros((T, p), np.int32)
    for s in range(p):
        for tt, (o, i, v) in enumerate(ops[s]):
            op_table[tt, s] = o
            micro_table[tt, s] = i
            chunk_table[tt, s] = phys(v)[1] if o else 0
    return Schedule(kind, m, p, cap, op_table, micro_table, vpp, chunk_table)


def validate_schedule(sched: Schedule) -> None:
    """Independent dependency/cap checker (used by tests)."""
    m, p, cap, vpp = sched.n_micro, sched.n_stages, sched.cap, sched.vpp
    V = p * vpp
    v_of, _ = sched.layout()
    f_at = {}
    bi_at = {}
    w_at = {}
    inflight = [0] * p
    for t in range(sched.n_ticks):
        for s in range(p):
            op = int(sched.op_table[t, s])
            i = int(sched.micro_table[t, s])
            if op == IDLE:
                continue
            v = v_of(s, int(sched.chunk_table[t, s]))
            if op == F_OP:
                assert v == 0 or f_at[(i, v - 1)] <= t - 1, (t, s, i, v)
                inflight[s] += 1
                assert inflight[s] <= cap, (t, s)
                f_at[(i, v)] = t
            elif op == BI_OP:
                assert f_at[(i, v)] <= t - 1, (t, s, i, v)
                if v < V - 1:
                    assert bi_at[(i, v + 1)] <= t - 1, (t, s, i, v)
                bi_at[(i, v)] = t
            elif op == W_OP:
                assert bi_at[(i, v)] <= t - 1, (t, s, i, v)
                inflight[s] -= 1
                w_at[(i, v)] = t
    for v in range(V):
        for i in range(m):
            assert (i, v) in f_at and (i, v) in bi_at and (i, v) in w_at


def _routing_tables(sched: Schedule):
    """Static per-(tick, stage) send routing derived from the layout.

    act_dir/cot_dir: _DIR_* for the payload an F/Bi op emits; *_rchunk: the
    chunk index the receiver stores into; is_last/is_first mark the loss-
    seeding and input-consuming virtual stages.
    """
    T, p = sched.op_table.shape
    v_of, phys = sched.layout()
    V = sched.n_virtual
    act_dir = np.zeros((T, p), np.int32)
    act_rc = np.zeros((T, p), np.int32)
    cot_dir = np.zeros((T, p), np.int32)
    cot_rc = np.zeros((T, p), np.int32)
    is_last = np.zeros((T, p), np.int32)
    is_first = np.zeros((T, p), np.int32)

    def direction(from_s, to_s):
        if to_s == from_s:
            return _DIR_LOCAL
        if to_s == (from_s + 1) % p:
            return _DIR_PLUS
        if to_s == (from_s - 1) % p:
            return _DIR_MINUS
        raise ValueError(f"non-neighbor hop {from_s}->{to_s}")

    for t in range(T):
        for s in range(p):
            op = int(sched.op_table[t, s])
            if op == IDLE:
                continue
            v = v_of(s, int(sched.chunk_table[t, s]))
            if op == F_OP:
                if v == V - 1:
                    is_last[t, s] = 1
                else:
                    ns, nc = phys(v + 1)
                    act_dir[t, s] = direction(s, ns)
                    act_rc[t, s] = nc
                if v == 0:
                    is_first[t, s] = 1
            elif op == BI_OP:
                if v > 0:
                    ps_, pc = phys(v - 1)
                    cot_dir[t, s] = direction(s, ps_)
                    cot_rc[t, s] = pc
                else:
                    is_first[t, s] = 1  # Bi at v0: its dx is the input grad
    return act_dir, act_rc, cot_dir, cot_rc, is_last, is_first


def _stage_permutation(sched: Schedule):
    """[p, vpp] table: entry (s, c) = the layer-order (virtual) index."""
    v_of, _ = sched.layout()
    return np.asarray([[v_of(s, c) for c in range(sched.vpp)]
                       for s in range(sched.n_stages)])


def pipeline_train_step(stage_params, x, labels, stage_fn, loss_fn, mesh,
                        axis_name="pp", schedule="1f1b", cap=None, vpp=1,
                        x_spec=None, param_spec=None, return_dx=False):
    """Run one microbatched fwd+bwd pass under an explicit schedule.

    stage_params: pytree with leaves stacked [n_stages*vpp, ...] in LAYER
    order (virtual-stage order). x/labels: [n_micro, mb, ...] (replicated).
    stage_fn(params_one_chunk, x_mb) -> y_mb (activation shape preserved);
    loss_fn(y_mb, labels_mb) -> scalar.

    Returns (loss, grads): loss = sum of per-microbatch losses (replicated);
    grads stacked [n_stages*vpp, ...] in layer order, sharded like the
    input. Pair with any optimizer. ``return_dx=True`` additionally returns
    d(loss)/d(x) (the input gradient, for an embedding in front).
    """
    jmesh = getattr(mesh, "jax_mesh", mesh)
    p = jmesh.shape[axis_name]
    m = x.shape[0]
    n_chunks = jax.tree.leaves(stage_params)[0].shape[0]
    if schedule == "zbv":
        vpp = 2
    if n_chunks != p * vpp:
        raise ValueError(
            f"stacked stage count {n_chunks} != pp({p}) * vpp({vpp})")
    sched = build_schedule(schedule, m, p, cap=cap, vpp=vpp)
    S = min(sched.cap, m)    # activation buffer slots per chunk
    perm = _stage_permutation(sched)             # [p, vpp] -> layer index
    inv = np.argsort(perm.reshape(-1))           # back to layer order
    # [V, ...] layer order -> [p, vpp, ...] layout order
    arranged = jax.tree.map(
        lambda l: l[perm.reshape(-1)].reshape(
            (p, vpp) + l.shape[1:]), stage_params)

    tables = tuple(jnp.asarray(a) for a in (
        (sched.op_table, sched.micro_table, sched.chunk_table)
        + _routing_tables(sched)))

    if x_spec is None:
        x_spec = P(*([None] * x.ndim))
    if param_spec is None:
        param_spec = jax.tree.map(lambda l: P(axis_name), stage_params)
    # layer-order spec P(pp, *rest) -> arranged [p, vpp, ...] spec
    # P(pp, None, *rest): trailing-dim shardings (e.g. mp) are preserved
    arranged_spec = jax.tree.map(
        lambda sp: P(*((tuple(sp)[:1] or (axis_name,))
                       + (None,) + tuple(sp)[1:])),
        param_spec, is_leaf=lambda s: isinstance(s, P))
    label_spec = P(*([None] * labels.ndim))

    body = functools.partial(
        _schedule_body, stage_fn=stage_fn, loss_fn=loss_fn,
        axis_name=axis_name, p=p, vpp=vpp, S=S, tables=tables)
    # partial-manual: only the pp axis is manual; dp/mp stay auto GSPMD
    # axes (batch sharding and Megatron TP collectives ride through, the
    # same contract as the circular pipeline path)
    mapped = shard_map(body, mesh=jmesh,
                       in_specs=(arranged_spec, x_spec, label_spec),
                       out_specs=(P(), arranged_spec, x_spec),
                       axis_names={axis_name}, check_vma=False)
    loss, grads_arranged, dx = mapped(arranged, x, labels)
    # [p, vpp, ...] -> [V, ...] layer order
    grads = jax.tree.map(
        lambda g: g.reshape((p * vpp,) + g.shape[2:])[inv], grads_arranged)
    if return_dx:
        return loss, grads, dx
    return loss, grads


def _schedule_body(params, x, labels, *, stage_fn, loss_fn, axis_name, p,
                   vpp, S, tables):
    (ops_tbl, mic_tbl, chk_tbl,
     adir_tbl, arc_tbl, cdir_tbl, crc_tbl, last_tbl, first_tbl) = tables
    r = lax.axis_index(axis_name)
    local = jax.tree.map(lambda l: l[0], params)   # [vpp, ...] leaves
    mb_shape = x.shape[1:]
    zero_mb = jnp.zeros(mb_shape, x.dtype)

    act = jnp.zeros((vpp, S) + mb_shape, x.dtype)  # saved chunk inputs
    rcv = jnp.zeros((vpp, S) + mb_shape, x.dtype)  # incoming activations
    cot = jnp.zeros((vpp, S) + mb_shape, x.dtype)  # incoming cotangents
    dxs0 = jnp.zeros_like(x)                       # input grads (stage of v0)
    grads0 = jax.tree.map(jnp.zeros_like, local)
    loss0 = jnp.zeros((), jnp.float32)

    fwd_perm = [(i, (i + 1) % p) for i in range(p)]
    bwd_perm = [(i, (i - 1) % p) for i in range(p)]
    no_send = (zero_mb, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
               jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))

    def tick(carry, t):
        act, rcv, cot, dxs, grads, loss = carry
        op = jnp.take(ops_tbl[t], r)
        micro = jnp.take(mic_tbl[t], r)
        c = jnp.take(chk_tbl[t], r)
        a_dir = jnp.take(adir_tbl[t], r)
        a_rc = jnp.take(arc_tbl[t], r)
        c_dir = jnp.take(cdir_tbl[t], r)
        c_rc = jnp.take(crc_tbl[t], r)
        lastf = jnp.take(last_tbl[t], r)
        firstf = jnp.take(first_tbl[t], r)
        slot = micro % S
        params_c = jax.tree.map(lambda l: jnp.take(l, c, axis=0), local)
        x_in = jnp.where(firstf > 0, x[micro], rcv[c, slot])
        saved = act[c, slot]
        dy = cot[c, slot]

        # send payload: (data, micro, recv_chunk, kind, valid-dir)
        def do_idle(act, rcv, cot, dxs, grads, loss):
            return act, rcv, cot, dxs, grads, loss, no_send, no_send

        def do_f(act, rcv, cot, dxs, grads, loss):
            y = stage_fn(params_c, x_in)
            # ONLY the last VIRTUAL stage evaluates loss_fn (which may
            # contain the full head projection) and seeds its cotangent;
            # lax.cond keeps every other F tick free of that cost
            l, dy_seed = lax.cond(
                lastf > 0,
                lambda yy: jax.value_and_grad(
                    lambda zz: loss_fn(zz, labels[micro]))(yy),
                lambda yy: (jnp.zeros((), jnp.float32),
                            jnp.zeros_like(yy)),
                y)
            act = act.at[c, slot].set(x_in)
            cot = cot.at[c, slot].set(
                jnp.where(lastf > 0, dy_seed, cot[c, slot]))
            loss = loss + l
            # ZBV turn: the next virtual stage lives on THIS device
            local_tgt = (a_dir == _DIR_LOCAL)
            rcv = rcv.at[a_rc, slot].set(
                jnp.where(local_tgt, y, rcv[a_rc, slot]))
            plus = (y, micro, a_rc,
                    jnp.full((), _KIND_ACT, jnp.int32),
                    (a_dir == _DIR_PLUS).astype(jnp.int32))
            minus = (y, micro, a_rc,
                     jnp.full((), _KIND_ACT, jnp.int32),
                     (a_dir == _DIR_MINUS).astype(jnp.int32))
            return act, rcv, cot, dxs, grads, loss, plus, minus

        def do_bi(act, rcv, cot, dxs, grads, loss):
            _, vjp = jax.vjp(lambda xx: stage_fn(params_c, xx), saved)
            dx = vjp(dy)[0]
            local_tgt = (c_dir == _DIR_LOCAL)
            cot = cot.at[c_rc, slot].set(
                jnp.where(local_tgt, dx, cot[c_rc, slot]))
            # Bi at virtual stage 0: dx IS d(loss)/d(x[micro])
            dxs = dxs.at[micro].set(
                jnp.where(firstf > 0, dx.astype(dxs.dtype), dxs[micro]))
            plus = (dx, micro, c_rc,
                    jnp.full((), _KIND_COT, jnp.int32),
                    (c_dir == _DIR_PLUS).astype(jnp.int32))
            minus = (dx, micro, c_rc,
                     jnp.full((), _KIND_COT, jnp.int32),
                     (c_dir == _DIR_MINUS).astype(jnp.int32))
            return act, rcv, cot, dxs, grads, loss, plus, minus

        def do_w(act, rcv, cot, dxs, grads, loss):
            _, vjp = jax.vjp(lambda pp: stage_fn(pp, saved), params_c)
            dw = vjp(dy)[0]
            grads = jax.tree.map(
                lambda g, d: g.at[c].add(d.astype(g.dtype)), grads, dw)
            return act, rcv, cot, dxs, grads, loss, no_send, no_send

        act, rcv, cot, dxs, grads, loss, plus, minus = lax.switch(
            op, [do_idle, do_f, do_bi, do_w], act, rcv, cot, dxs, grads,
            loss)

        # one +1-ring hop and one -1-ring hop per tick; payloads carry
        # (data, micro, chunk, kind, valid) and wrap-arounds are dropped
        # via the validity tag
        rp = lax.ppermute(plus, axis_name, fwd_perm)
        rm = lax.ppermute(minus, axis_name, bwd_perm)
        for (data, m_, rc_, kind, val) in (rp, rm):
            s_ = m_ % S
            take_act = (val > 0) & (kind == _KIND_ACT)
            take_cot = (val > 0) & (kind == _KIND_COT)
            rcv = rcv.at[rc_, s_].set(jnp.where(take_act, data, rcv[rc_, s_]))
            cot = cot.at[rc_, s_].set(jnp.where(take_cot, data, cot[rc_, s_]))
        return (act, rcv, cot, dxs, grads, loss), None

    (_, _, _, dxs, grads, loss), _ = lax.scan(
        tick, (act, rcv, cot, dxs0, grads0, loss0),
        jnp.arange(ops_tbl.shape[0]))
    total = lax.psum(loss, axis_name)  # only the loss-owning stage adds
    # dxs is nonzero only on the stage holding virtual stage 0
    dx_total = lax.psum(dxs, axis_name)
    return total, jax.tree.map(lambda g: g[None], grads), dx_total


def scheduled_pipeline_loss(stage_params, x_embedded, labels, stage_fn,
                            loss_fn, mesh, axis_name="pp", schedule="zbh1",
                            cap=None, vpp=1, x_spec=None, param_spec=None):
    """Differentiable wrapper: composes the fused fwd+bwd executor with
    OUTER autodiff (an embedding in front of the pipeline, an optimizer
    jitted around it).

    The executor produces (loss, param-grads, input-grads) in one pass;
    since every downstream use of a scalar loss is linear in its cotangent,
    the custom VJP simply scales the stored grads — the same contract the
    reference's Job-based executor exposes to its optimizer stage.
    """
    def _run_all(stage_params, x_embedded):
        return pipeline_train_step(
            stage_params, x_embedded, labels, stage_fn, loss_fn, mesh,
            axis_name=axis_name, schedule=schedule, cap=cap, vpp=vpp,
            x_spec=x_spec, param_spec=param_spec, return_dx=True)

    @jax.custom_vjp
    def _run(stage_params, x_embedded):
        loss, _, _ = _run_all(stage_params, x_embedded)
        return loss

    def _fwd(stage_params, x_embedded):
        loss, grads, dx = _run_all(stage_params, x_embedded)
        return loss, (grads, dx)

    def _bwd(res, ct):
        grads, dx = res
        return (jax.tree.map(lambda g: g * ct, grads), dx * ct)

    _run.defvjp(_fwd, _bwd)
    return _run(stage_params, x_embedded)


__all__ = ["build_schedule", "validate_schedule", "pipeline_train_step",
           "scheduled_pipeline_loss", "Schedule", "forward_bubble_fraction",
           "IDLE", "F_OP", "BI_OP", "W_OP"]
