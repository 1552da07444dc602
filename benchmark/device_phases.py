"""Device time by phase: the join behind the ``*_dev_pct`` readers that
split what XLA wrote (``fusion``, ``copy``, ``convolution_*``) by the
phase of the program that owns it.

The program names its phases (``paddle_tpu/profiler/phases.py``:
``jax.named_scope("phase.<x>")`` in the layer bodies) and keeps, for each
step executable, the table *instruction -> (phase, pass)* read off the
optimised HLO's ``metadata={op_name=...}``; the traced window's
``run["trace"]["op_seconds"]`` is keyed by the same instruction names
(``fusion.123``), clipped to the window and averaged over chips. A share
is 100 x the phases' seconds / ``trace["busy_s"]``, the convention of
``ragged_attn_dev_pct``. ``while`` / ``conditional`` / ``call`` events
span their body's and are left out, as ``collective_exposed_pct`` leaves
them out, so the shares of a run and its unscoped one add up to at most
100. A fusion is charged to the phase its own metadata names, its
root's.

Every function returns None where there is nothing to read: a run
without a trace, a program without ``profiler/phases.py`` (the parent of
the PR that added it), no step executable registered, or a table that
names no phase at all (an executable loaded from a compile cache that a
tree without the scopes filled).
"""
import sys
import time

UNSCOPED = None


def charges(run, executable):
    """``{(phase, pass): seconds}`` of the traced window for the newest
    registered executable whose name starts with ``executable``
    (``"serve.step"``, ``"train.step"``), or None (above)."""
    trace = run.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    try:
        from paddle_tpu.profiler import phases
    except ImportError:
        return None
    name = phases.newest(executable)
    if name is None:
        return None
    t0 = time.perf_counter()
    fresh = name not in _seen
    table = phases.table(name)
    t1 = time.perf_counter()
    if not any(phase is not None for phase, _ in table.values()):
        return None
    charged = phases.charge(trace["op_seconds"], name)
    if fresh:
        # what tracing costs when it is on: the text and its parse are
        # paid once a process, here, after the window has closed
        _seen.add(name)
        t2 = time.perf_counter()
        # seconds that carry a phase their own metadata did not name
        # (the table's first-reader rule), containers aside as above
        rule = phases.placed_by_reader(name)
        placed = sum(phases.charge(
            {n: s for n, s in trace["op_seconds"].items() if n in rule},
            name).values())
        print(f"device_phases: {name}: table of {len(table)} instructions "
              f"in {t1 - t0:.3f} s (as_text + parse), join of "
              f"{len(trace['op_seconds'])} traced names in {t2 - t1:.4f} s; "
              f"the first-reader rule placed "
              f"{100 * placed / trace['busy_s']:.3f} % of busy",
              file=sys.stderr)
    return charged


_seen = set()


def share(charged, busy_s, phases=None, passes=None):
    """100 x seconds / ``busy_s`` of the charges whose phase is in
    ``phases`` (None: every one; ``(UNSCOPED,)``: the unscoped) and whose
    pass is in ``passes`` (None: every one)."""
    total = sum(s for (phase, which), s in charged.items()
                if (phases is None or phase in phases)
                and (passes is None or which in passes))
    return 100.0 * total / busy_s


def read(run, executable, phases=None, passes=None):
    """A reader's whole body: the share, or None."""
    charged = charges(run, executable)
    if charged is None:
        return None
    return share(charged, run["trace"]["busy_s"], phases, passes)
