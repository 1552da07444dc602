"""Device time by phase: the layer bodies name their phases, the program
keeps each step executable's instruction -> phase table, and a reader
joins it with a trace's per-instruction seconds.

    with phases.phase("attn.qkv"):                # in a layer body
        q = x @ w_q
    phases.register("serve.step", compiled)       # the owner, first launch
    phases.charge(op_seconds)                     # a reader, any time later
    # -> {("mlp", "fwd"): 1.9, ("attn.qkv", "fwd"): 0.4, (None, "fwd"): 0.1}

A phase is ``jax.named_scope("phase." + name)``: metadata on the lowered
instructions, nothing in the compiled program and no cost after tracing
(``core/phase_scope.py`` holds the scope, the vocabulary and the stack of
open phases, where the tape can reach them; this module hands them on).
The optimised HLO keeps it on every instruction, fusions included:
``metadata={op_name="jit(step)/jvp(phase.mlp)/dot_general"}``; JAX wraps
the forward's stack for the backward (``transpose(jvp(phase.mlp))``) and
marks a recomputed forward ``rematted_computation`` (``jax.checkpoint``
does; the tape's own replay, ``distributed/fleet/recompute.py``, opens
the same scope). So the compiled step can say which phase owns an
instruction, and a profiler trace says how long each instruction ran.

A FUSION IS CHARGED TO THE PHASE ITS OWN ``metadata`` NAMES, WHICH IS
ITS ROOT'S: where the compiler fuses a producer of one phase into a
consumer of the next (a norm's scale into the projection that reads
it), the whole fusion goes to the consumer's phase. An instruction the
compiler left without any metadata (a layout copy, a prefetch, a fusion
of its own making) is charged where its first reader is (:func:`parse`),
and the table says which it placed so (:func:`placed_by_reader`).

The registry holds ``jax.stages.Compiled`` handles, strongly, and
nothing that holds weights: it outlives the engine or ``TrainStep`` that
registered (the benchmark's readers run after their runner has
returned). ``register`` does nothing else; the text is taken
(``as_text()``) and parsed (``jit/hlo_forensics.instruction_metadata``)
once a handle, when somebody first asks for its table.

JAX's persistent compilation cache leaves metadata out of its key
(``jax_compilation_cache_include_metadata_in_key`` is False): an
executable cached by a tree without the scopes loads without them and
reads 100 % unscoped (docs/OBSERVABILITY.md).
"""
from __future__ import annotations

import functools
import re

import jax

from ..core.phase_scope import (PHASES, REMAT_SCOPE, open_phase, phase,
                                reenter)

PASSES = ("fwd", "bwd", "remat")
#: instructions whose trace event spans their body's events
CONTAINERS = ("while", "conditional", "call")

_PHASE_RE = re.compile(r"phase\.([a-z]+(?:\.[a-z]+)*)")


def scoped(name):
    """Decorator: the whole function runs under :func:`phase` ``name``."""
    def wrap(fn):
        @functools.wraps(fn)
        def under_phase(*args, **kwargs):
            with phase(name):       # the module's, so a test can patch it
                return fn(*args, **kwargs)
        return under_phase
    return wrap


# ---------------------------------------------------------------------
# the registry: executable name -> compiled handle -> table
# ---------------------------------------------------------------------
_handles: dict = {}      # name -> jax.stages.Compiled
#: name -> (handle, {instruction: (phase, pass)}, containers, placed)
_tables: dict = {}


def register(executable_name, compiled):
    """Keep ``compiled`` (a ``jax.stages.Compiled``: anything whose
    ``as_text()`` gives an optimised HLO module) as the newest handle of
    ``executable_name``. Called by the owner at its first launch of a
    step executable; costs a dict store."""
    _handles.pop(executable_name, None)     # the newest is the last key
    _handles[executable_name] = compiled


def launch_specs(args):
    """``args`` (a launch's own, read BEFORE the call that donates some
    of them) as ``jax.ShapeDtypeStruct``s of the same shapes, dtypes,
    weak types and, where the array is committed, shardings: lowering
    the jitted step over them after its first call hits JAX's in-memory
    trace, lowering and executable caches (about a millisecond), traces
    nothing again and touches no deleted array."""
    def spec(a):
        if isinstance(a, jax.Array):
            return jax.ShapeDtypeStruct(
                a.shape, a.dtype,
                weak_type=getattr(a, "weak_type", False),   # a key has none
                sharding=a.sharding if a.committed else None)
        if hasattr(a, "shape") and hasattr(a, "dtype"):     # numpy
            return jax.ShapeDtypeStruct(a.shape, a.dtype)
        return a                                            # a scalar
    return jax.tree.map(spec, args)


def registered() -> list:
    """Names with a handle, oldest registration first."""
    return list(_handles)


def newest(prefix=""):
    """The name registered last among those that start with ``prefix``
    (``"serve.step"`` finds a cluster's ``"serve.step:3"`` too), or None.
    """
    return next((n for n in reversed(_handles) if n.startswith(prefix)),
                None)


def classify(op_name):
    """``(phase, pass)`` of one ``op_name``: the INNERMOST
    ``phase.<x>`` component (None without one); ``remat`` under a
    ``rematted_computation`` scope, else ``bwd`` under a
    ``transpose(``, else ``fwd``."""
    if not op_name:
        return None, "fwd"
    found = _PHASE_RE.findall(op_name)
    which = found[-1] if found and found[-1] in PHASES else None
    if REMAT_SCOPE in op_name:
        return which, "remat"
    return which, "bwd" if "transpose(" in op_name else "fwd"


def parse(hlo_text):
    """``({instruction: (phase, pass)}, containers, placed)`` of one
    optimised HLO module; ``containers`` the names of its
    :data:`CONTAINERS` instructions (by opcode: a ``conditional`` may be
    named ``cond.1.clone``).

    An instruction the compiler left WITHOUT A TRACED OP'S METADATA (a
    layout ``copy`` or ``reshape`` it put in, a prefetch's
    ``copy-start`` / ``copy-done``, a fusion it built itself) is charged
    where the first instruction that reads it is charged, failing that
    where the first it reads is: it exists for its reader. ``placed``
    names the instructions this rule gave a phase, so a reader can say
    how much of a phase's time was read off metadata and how much was
    placed (:func:`placed_by_reader`). One whose metadata names a traced
    op and no phase stays unscoped."""
    from ..jit.hlo_forensics import instruction_metadata
    tbl, containers, bare, users, placed = {}, set(), {}, {}, set()
    for name, opcode, op_name, refs in instruction_metadata(hlo_text):
        tbl[name] = classify(op_name)
        if opcode in CONTAINERS:
            containers.add(name)
        if op_name is None or "/" not in op_name:
            # none, or an argument's label (``params['w']`` on the copy
            # the compiler makes of it): no traced op's stack
            bare[name] = refs
        for ref in refs:
            users.setdefault(ref, []).append(name)
    moved = True
    while moved:            # chains: a tuple's element, then its copy
        moved = False
        for name, refs in bare.items():
            if tbl[name][0] is not None:
                continue
            near = users.get(name, []) + [r for r in refs if r in tbl]
            got = next((tbl[n] for n in near if tbl[n][0] is not None), None)
            if got is not None:
                tbl[name], moved = got, True
                placed.add(name)
    return tbl, frozenset(containers), frozenset(placed)


def text(executable_name=None):
    """The optimised HLO text of a registered executable (the newest
    registered one by default), or None: what the table is parsed from,
    for an operator who wants an instruction's whole ``op_name``
    (``jit/hlo_forensics.instruction_metadata``)."""
    if executable_name is None:
        executable_name = newest()
    handle = _handles.get(executable_name)
    return None if handle is None else handle.as_text()


def _entry(executable_name):
    if executable_name is None:
        executable_name = newest()
    handle = _handles.get(executable_name)
    if handle is None:
        return None
    cached = _tables.get(executable_name)
    if cached is None or cached[0] is not handle:
        cached = _tables[executable_name] = (
            handle, *parse(handle.as_text()))
    return cached


def table(executable_name=None):
    """``{instruction: (phase, pass)}`` of a registered executable (the
    newest registered one by default), or None where none is registered.
    The text is taken and parsed at the first call for a handle."""
    cached = _entry(executable_name)
    return cached and cached[1]


def placed_by_reader(executable_name=None):
    """The instructions of :func:`table` whose phase was not read off
    their own metadata but placed by :func:`parse`'s first-reader rule
    (a frozenset), or None where none is registered: coverage by
    metadata alone is the scoped share less these."""
    cached = _entry(executable_name)
    return cached and cached[3]


def charge(op_seconds, executable_name=None):
    """``{instruction: seconds}`` (any trace's; the benchmark's
    ``run["trace"]["op_seconds"]``) -> ``{(phase, pass): seconds}``.
    Containers (``while``, ``conditional``, ``call``: their event spans
    their body's) are left out, so one chip's charges add up to no more
    than its busy time. An instruction the table does not know (another
    executable's) is charged to ``(None, "fwd")``, the unscoped share.
    None where no executable is registered."""
    cached = _entry(executable_name)
    if cached is None:
        return None
    _, tbl, containers, _ = cached
    out: dict = {}
    for name, seconds in op_seconds.items():
        head, _, tail = name.rpartition(".")
        if name in containers or \
                (head if head and tail.isdigit() else name) in CONTAINERS:
            continue
        key = tbl.get(name, (None, "fwd"))
        out[key] = out.get(key, 0.0) + seconds
    return out


def clear():
    """Forget every handle (tests)."""
    _handles.clear()
    _tables.clear()


__all__ = ["PHASES", "PASSES", "REMAT_SCOPE", "CONTAINERS", "phase", "scoped",
           "open_phase", "reenter", "launch_specs", "register", "registered",
           "newest", "classify", "parse", "text", "table", "placed_by_reader",
           "charge", "clear"]
