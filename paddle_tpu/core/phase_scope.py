"""The scope a phase of the model is traced under, and which one is open.

``phase("mlp")`` is ``jax.named_scope("phase.mlp")``: metadata on the
lowered instructions, nothing in the compiled program. The open phases
of a thread are kept on a stack besides, because the tape defers work
past the forward's ``with`` (``core/dispatch.py``: a node's ``vjp_fn``;
``distributed/fleet/recompute.py``: the replayed forward): it snapshots
:func:`open_phase` beside the autocast state and runs the deferred work
under :func:`reenter`.

A phase here is a part of the MODEL on the device's instructions;
``profiler/spans.py``'s ``Span.phase`` cuts a HOST span into sequential
children and is unrelated.

Imports jax alone, so ``core`` can use it; the registry, the table and
the vocabulary's readers are ``profiler/phases.py``, which hands these
names on.
"""
from __future__ import annotations

import contextlib
import threading

import jax

#: the closed vocabulary, serving and training alike (PERF.md section 3
#: names the metric each is for)
PHASES = ("embed", "norm", "attn.qkv", "attn.core", "attn.out", "mlp",
          "moe.route", "moe.dispatch", "moe.experts", "moe.combine",
          "head", "guard", "sample", "loss", "optimizer")
#: the scope of a recomputed forward, ``jax.checkpoint``'s own name for it
REMAT_SCOPE = "rematted_computation"

_tls = threading.local()


def _open() -> list:
    try:
        return _tls.open
    except AttributeError:
        _tls.open = []
        return _tls.open


@contextlib.contextmanager
def phase(name):
    """``jax.named_scope("phase." + name)``, ``name`` one of
    :data:`PHASES`. Phases nest; an instruction belongs to the innermost.
    """
    if name not in PHASES:
        raise ValueError(f"phase {name!r} is not one of {PHASES}")
    stack = _open()
    stack.append(name)
    try:
        with jax.named_scope("phase." + name):
            yield
    finally:
        stack.pop()


def open_phase():
    """The innermost phase open on this thread, or None."""
    stack = _open()
    return stack[-1] if stack else None


def reenter(name, remat=False):
    """The scope deferred work runs under at backward time: the phase
    that was open when its forward ran (``name`` from
    :func:`open_phase`, None for none) and, with ``remat``, the mark of
    a recomputed forward."""
    ctx = contextlib.ExitStack()
    if remat:
        ctx.enter_context(jax.named_scope(REMAT_SCOPE))
    if name is not None:
        ctx.enter_context(phase(name))
    return ctx
