"""A dropless routed expert layer that is told which experts it holds —
the serving path's routed feed-forward (pure ``jax.numpy`` over raw
arrays, like ``models/generation.py``'s bodies). The router scores with a
sigmoid (:func:`route_sigmoid`) or a softmax (:func:`route_softmax`).

The layer a chip runs in an expert-parallel deployment: the router scores
ALL ``router_width`` experts in float32 and picks ``top_k`` a token; this
chip holds the ``held`` experts ``[first, first + held)`` and computes the
part of ``y = sum_i g_i E_i(x)`` whose experts it holds. The other chips'
parts, and the exchange that would sum them, are not stood in for.

Nothing is dropped: the token-expert pairs that land here are sorted by
expert into a buffer sized for the worst routing (every token sending all
its picks here), each expert's rows padded to whole row tiles, and the
three projections run as grouped matrix products over it
(``kernels/grouped_matmul.py``). ``incubate/distributed/models/moe``'s
GShard gate, which ``models/llama_moe.py`` trains with, gives each expert
``capacity_factor`` x its fair share of slots and drops the rest; a
serving step must not.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..kernels.grouped_matmul import grouped_matmul
from ..profiler import phases

F32 = jnp.float32


def limit_to_groups(choice, n_group, topk_group):
    """The group limit of a ``noaux_tc`` router: the ``[T, E]`` choice
    scores' ``E`` experts are ``n_group`` equal groups of neighbours, a
    group's score is the sum of its two largest choice scores, the
    ``topk_group`` best groups are kept and the other groups' scores
    become ``-inf`` (never chosen; the published code writes 0 there,
    the same choice wherever the kept groups hold ``top_k`` positive
    scores, as sigmoid scores with a zero bias always do)."""
    t, e = choice.shape
    groups = choice.reshape(t, n_group, e // n_group)
    score = jnp.sum(jax.lax.top_k(groups, 2)[0], -1)          # [T, n_group]
    _, kept = jax.lax.top_k(score, topk_group)
    keep = jnp.zeros((t, n_group), bool).at[
        jnp.arange(t)[:, None], kept].set(True)
    return jnp.where(keep[:, :, None], groups, -jnp.inf).reshape(t, e)


@phases.scoped("moe.route")
def route_sigmoid(x, router, bias, *, top_k, scaling, norm_topk_prob=True,
                  n_group=1, topk_group=1):
    """Experts and gates of every token. ``x [T, h]``; ``router [h,
    router_width]``; ``bias [router_width]`` (the score-correction bias:
    it steers the CHOICE, the gate is the raw score). Scores in float32
    at full precision whatever ``x`` is stored in: a near-tie between
    the k-th and (k+1)-th expert flips on less. ``n_group`` > 1 (static)
    limits the choice to the ``topk_group`` best groups
    (:func:`limit_to_groups`); 1 lowers what it lowered before. Returns
    ``(idx [T, k] int32, gates [T, k] float32)``."""
    s = jax.nn.sigmoid(jnp.dot(x.astype(F32), router.astype(F32),
                               precision="highest"))
    choice = s + bias.astype(F32)
    if n_group > 1:
        choice = limit_to_groups(choice, n_group, topk_group)
    _, idx = jax.lax.top_k(choice, top_k)
    g = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk_prob:
        g = g / jnp.sum(g, -1, keepdims=True)
    return idx.astype(jnp.int32), g * scaling


@phases.scoped("moe.route")
def route_softmax(x, router, *, top_k, norm_topk_prob=True):
    """Experts and gates of every token under a softmax router: ``s =
    softmax(x router)`` over all ``router_width`` experts in float32 at
    full precision, the ``top_k`` largest chosen, their gates ``s``
    renormalised over the chosen where ``norm_topk_prob``. Returns
    ``(idx [T, k] int32, gates [T, k] float32)``."""
    s = jax.nn.softmax(jnp.dot(x.astype(F32), router.astype(F32),
                               precision="highest"), -1)
    g, idx = jax.lax.top_k(s, top_k)
    if norm_topk_prob:
        g = g / jnp.sum(g, -1, keepdims=True)
    return idx.astype(jnp.int32), g


def shared_expert_gate(x, w):
    """The shared expert's gate of a gated shared expert: ``sigmoid(x
    w)`` a token, ``x [T, h]``, ``w [h, 1]``, in float32 (``[T, 1]``)."""
    return jax.nn.sigmoid(jnp.dot(x.astype(F32), w.astype(F32),
                                  precision="highest"))


def buffer_rows(tokens, top_k, held, tm):
    """Rows of the sorted buffer: every token's picks landing here (at
    most ``min(top_k, held)`` a token), and a partly filled last tile
    an expert."""
    worst = tokens * min(top_k, held)
    return -(-worst // tm) * tm + held * tm


@phases.scoped("moe.dispatch")
def dispatch_plan(idx, live, *, first, held, tm):
    """Where each token-expert pair goes. ``idx [T, k]`` global expert
    ids; ``live [T]`` bool (a dead slot of a packed step routes
    nowhere). Returns a dict: ``here [T, k]`` bool (the pair's expert
    is held and its token live), ``pair_row [T, k]`` the pair's row in
    the sorted buffer, ``src [M]`` the token each buffer row reads,
    ``tile_group [M / tm]``, ``live_tiles`` and ``counts [held]`` tokens
    an expert."""
    t, k = idx.shape
    m = buffer_rows(t, k, held, tm)
    here = (idx >= first) & (idx < first + held) & live[:, None]
    local = jnp.where(here, idx - first, held).reshape(-1)   # held = away
    order = jnp.argsort(local, stable=True)                  # by expert
    counts = jnp.zeros((held + 1,), jnp.int32).at[local].add(1)[:held]
    padded = -(-counts // tm) * tm
    ends = jnp.cumsum(padded)
    sorted_e = local[order]
    start_sorted = jnp.cumsum(counts) - counts
    e = jnp.minimum(sorted_e, held - 1)
    row = jnp.where(sorted_e < held,
                    (ends - padded)[e] + jnp.arange(t * k) - start_sorted[e],
                    m)                                        # away: dropped
    src = jnp.zeros((m,), jnp.int32).at[row].set(
        (order // k).astype(jnp.int32), mode="drop")
    pair_row = jnp.zeros((t * k,), jnp.int32).at[order].set(
        jnp.minimum(row, m - 1).astype(jnp.int32)).reshape(t, k)
    tile_group = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(m // tm) * tm, side="right"),
        held - 1).astype(jnp.int32)
    return {"here": here, "pair_row": pair_row, "src": src,
            "tile_group": tile_group, "live_tiles": ends[-1] // tm,
            "counts": counts}


def dropless_experts(x, idx, gates, live, gate_w, up_w, down_w, *, first,
                     tm=128, interpret=False):
    """This chip's part of the routed sum. ``x [T, h]``; ``idx``/``gates
    [T, k]`` from :func:`route_sigmoid`; ``gate_w``/``up_w [held, h,
    m]``, ``down_w [held, m, h]`` the held experts' SwiGLU weights.
    Returns ``(y [T, h], stats [3] int32)``: ``y[t] = sum over t's
    picks held here of gate x E(x[t])``, and (pairs computed here, held
    experts with at least one token, most tokens any one expert got)."""
    held = gate_w.shape[0]
    plan = dispatch_plan(idx, live, first=first, held=held, tm=tm)
    with phases.phase("moe.dispatch"):
        xs = x[plan["src"]]                                   # [M, h]

    def mm(a, w):
        return grouped_matmul(a, w, plan["tile_group"], plan["live_tiles"],
                              tm=tm, interpret=interpret)
    with phases.phase("moe.experts"):
        act = (jax.nn.silu(mm(xs, gate_w).astype(F32))
               * mm(xs, up_w).astype(F32)).astype(x.dtype)
        out = mm(act, down_w)                                 # [M, h]
    # every row of ``out`` is finite (dead tiles are zeros, a live tile's
    # padding rows read a real token), so a gate of 0 masks a pair away
    # (an elementwise product: a dot would round the f32 gates on the chip)
    with phases.phase("moe.combine"):
        y = jnp.sum(out[plan["pair_row"]].astype(F32)
                    * jnp.where(plan["here"], gates, 0.0)[..., None], 1)
        c = plan["counts"]
        stats = jnp.stack([jnp.sum(c), jnp.sum(c > 0), jnp.max(c)])
        return y.astype(x.dtype), stats.astype(jnp.int32)


__all__ = ["buffer_rows", "dispatch_plan", "dropless_experts",
           "limit_to_groups", "route_sigmoid", "route_softmax",
           "shared_expert_gate"]
