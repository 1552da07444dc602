"""HLO fusion forensics: measure fusion as a property, not a hope.

XLA's fusion pass is the single biggest lever between "the program the
trace describes" and "the kernels the chip launches" (the Operator
Fusion in XLA analysis, PAPERS.md): a refactor — or a JAX/XLA upgrade —
that splits a hot fused region doubles the HBM traffic of everything
that used to stay in registers, and nothing in the test suite notices
because the VALUES are identical. This module turns the compiled HLO
text (``jit.TrainStep(capture_hlo=True)``, ``LLMEngine.
ragged_step_hlo()``) into counted, gateable numbers:

- ``fusion_count`` — fusion instruction defs across the whole module
  (while/scan bodies included): a defused region shows up as MORE
  fusions (the one region becomes several) or more unfused entry ops;
- ``kernel_count`` — entry-computation instruction defs that launch
  work (everything except parameter/constant/tuple/get-tuple-element/
  bitcast): the per-step launch/thunk count proxy;
- ``fusion_bytes_total`` / ``fusion_bytes_max`` — bytes touched per
  fused region (result + operand buffers read off the instruction's
  inline shapes), summed and worst-case: a split region re-materializes
  its intermediate, so bytes-touched RISES when fusion regresses;
- ``fusion_kinds`` — kLoop/kInput/kOutput breakdown.

The counts are those of one XLA build on one host: compare two programs
compiled side by side (tests/test_prefill_megakernel.py does), never a
count with a number recorded elsewhere. ``launch_stats`` below reads the
UNOPTIMIZED lowering, whose counts are the program's structure and repeat
everywhere (tests/test_hlo_forensics.py, ``LLMEngine.launch_stats``).
"""
from __future__ import annotations

import re

#: bytes per element for the HLO shape dtypes this stack emits
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

#: `f32[8,128]` / `s32[]` shape tokens (layout suffixes `{1,0}` ignored)
_SHAPE_RE = re.compile(
    r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")

#: one instruction definition: `%name = <shape-or-tuple> opname(`. The
#: opcode is the first ` word(` after the `=`: a tuple shape's members
#: are followed by `[`, and a TPU layout's tile (`{1,0:T(8,128)}`) by no
#: space, so neither is taken for it
_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?(?P<sigil>%?)(?P<name>[\w.\-]+) = .*? "
    r"(?P<op>[a-z][\w\-]*)\(")

_OP_NAME_RE = re.compile(r'\bop_name="([^"]*)"')

#: `, metadata={op_name="..." source_file="..." ...}` of a def line
_METADATA_RE = re.compile(r",? ?\bmetadata=\{[^}]*\}")

#: the names a def line refers to: operands, and the computations it
#: calls. A module printed with the `%` sigil marks them; one printed
#: without it gives bare words, of which an attribute's key (`slice=`)
#: is none and the rest are told from shapes and values by being the
#: name of an instruction (:func:`instruction_metadata`)
_REF_RE = re.compile(r"(?<![\w.\-%])(%?)([\w.\-]+)(?![\w.\-=])")

_FUSION_KIND_RE = re.compile(r"kind=(k\w+)")

#: entry-computation defs that launch no work — everything else is a
#: kernel/thunk proxy on the CPU/TPU thunk schedule
_FREE_OPS = ("parameter", "constant", "tuple", "get-tuple-element",
             "bitcast")


def shape_bytes(text: str) -> int:
    """Total bytes of every shape token in ``text`` (a def line's
    result type + inline operand types)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _entry_lines(hlo_text: str):
    """Instruction lines of the ENTRY computation only."""
    out, in_entry = [], False
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY "):
            in_entry = True
            continue
        if in_entry:
            if line.startswith("}"):
                break
            out.append(line)
    return out


def fusion_stats(hlo_text: str) -> dict:
    """Parse one compiled HLO module's text into the fusion-forensics
    numbers (see module docstring). Pure text analysis — no device
    work, deterministic for a pinned compiler."""
    fusion_bytes = []
    fusion_kinds: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if m is None or m.group("op") != "fusion":
            continue
        fusion_bytes.append(shape_bytes(line.split(", calls=")[0]))
        km = _FUSION_KIND_RE.search(line)
        if km:
            fusion_kinds[km.group(1)] = fusion_kinds.get(km.group(1), 0) + 1
    kernels = 0
    instructions = 0
    for line in _entry_lines(hlo_text):
        m = _DEF_RE.match(line)
        if m is None:
            continue
        instructions += 1
        if m.group("op") not in _FREE_OPS:
            kernels += 1
    return {
        "fusion_count": len(fusion_bytes),
        "kernel_count": kernels,
        "entry_instruction_count": instructions,
        "fusion_bytes_total": sum(fusion_bytes),
        "fusion_bytes_max": max(fusion_bytes, default=0),
        "fusion_kinds": dict(sorted(fusion_kinds.items())),
    }


def instruction_metadata(hlo_text: str):
    """``(name, opcode, op_name, refs)`` of every instruction definition
    of the module, whatever computation holds it: the entry's, a fusion's, a
    scanned loop's body, a ``conditional``'s branches (instruction names
    are unique in a module). ``op_name`` is what the instruction's
    ``metadata={op_name="jit(step)/.../<scope>/<primitive>"}`` carries:
    the ``jax.named_scope`` stack it was traced under, None where the
    compiler wrote none. A fusion's is its root's. ``refs``: the names
    the definition refers to, in order: its operands and, in a module
    printed with the ``%`` sigil, the computations it calls."""
    defs = [(m, line) for m, line in
            ((_DEF_RE.match(line), line) for line in hlo_text.splitlines())
            if m is not None]
    names = {m.group("name") for m, _ in defs}
    sigils = any(m.group("sigil") for m, _ in defs)
    for m, line in defs:
        meta = _OP_NAME_RE.search(line, m.end())
        refs = tuple(
            ref for sigil, ref in
            _REF_RE.findall(_METADATA_RE.sub("", line[m.end():]))
            if (sigil if sigils else ref in names))
        yield m.group("name"), m.group("op"), meta and meta.group(1), refs


#: the module's index of source locations, which ``stack_frame_id`` in
#: an instruction's metadata points into: sections of the text's head
_FRAME_SECTIONS = ("FileNames", "FunctionNames", "FileLocations",
                   "StackFrames")


def strip_metadata(hlo_text: str) -> str:
    """The module's text less every ``metadata={...}`` and less the
    index of source locations they point into: two programs that differ
    in scopes and source lines alone strip to the same bytes."""
    out, skipping = [], False
    for line in _METADATA_RE.sub("", hlo_text).splitlines(keepends=True):
        if line.strip() in _FRAME_SECTIONS:
            skipping = True
        elif skipping and not line.strip():
            skipping = False
            continue
        if not skipping:
            out.append(line)
    return "".join(out)


#: the launch-accounting marker: rsqrt appears in this stack's decode
#: bodies ONLY inside rms_norm (attention scales by a python-float
#: 1/sqrt(d), sampling/PRNG/softmax emit none), so counting rsqrt ops
#: in an UNOPTIMIZED lowering counts rms_norm sites — a fixed number
#: per decoder-layer body plus one final norm
_MARKER_RE = re.compile(r"\brsqrt\b")


def launch_stats(program_text: str, *, num_layers,
                 markers_per_body=2, overhead_markers=1,
                 tokens_per_invocation=1) -> dict:
    """Launch accounting over an UNOPTIMIZED StableHLO lowering
    (``jit(f).lower(args).as_text()``): how many times does the decoder
    layer body appear as a distinct site in the program?

    The measurable is structural, not a fusion heuristic: an unrolled
    layer loop inlines the body ``num_layers`` times; a ``lax.scan``
    over stacked weights emits ONE body inside ``stablehlo.while``.
    Each body carries ``markers_per_body`` rms_norm (rsqrt) markers and
    the program carries ``overhead_markers`` non-layer markers (the
    final norm), so

        layer_body_sites = (markers - overhead) / markers_per_body
        launches_per_token = layer_body_sites / tokens_per_invocation

    ``tokens_per_invocation`` > 1 accounts a burst executable, whose
    one invocation's while_loop covers that many tokens per row —
    model-scope burst decode reaches 1/burst_tokens launches per token.
    ``collapsed`` is the gateable headline: True iff the layer loop
    lives inside the program (<= 1 body site). Raises ValueError when
    the marker count is inconsistent with the constants (e.g. a body
    gained a norm without the caller re-deriving markers_per_body) —
    silently mis-dividing would fabricate a launch count.
    """
    markers = len(_MARKER_RE.findall(program_text))
    sites_num = markers - int(overhead_markers)
    if sites_num < 0 or sites_num % int(markers_per_body):
        raise ValueError(
            f"launch_stats: {markers} rsqrt markers do not decompose as "
            f"{overhead_markers} overhead + N x {markers_per_body} "
            f"per-body markers — the traced body changed; re-derive the "
            f"marker constants")
    sites = sites_num // int(markers_per_body)
    return {
        "marker_count": markers,
        "layer_body_sites": sites,
        "num_layers": int(num_layers),
        "launches_per_token": sites / float(tokens_per_invocation),
        "collapsed": sites <= 1,
    }


def mixed_launch_stats(program_text: str, *, num_layers,
                       kinds, overhead_markers=1,
                       tokens_per_invocation=1,
                       exclusive=False) -> dict:
    """Launch accounting for a MIXED invocation — one program whose
    body contains more than one kind of decoder-layer body (the
    serving ragged step runs prefill-chunk rows and decode rows in the
    same fixed-shape executable).

    ``kinds`` maps a body-kind name to its markers-per-body count, e.g.
    ``{"prefill": 2, "decode": 2}``. Each kind's site count is
    structural — ``0`` (absent), ``1`` (scan-collapsed) or
    ``num_layers`` (unrolled) — so the total marker count must
    decompose as

        markers = overhead + sum_k sites_k * markers_per_body_k

    with every ``sites_k`` in ``{0, 1, num_layers}`` (``{1,
    num_layers}`` when ``exclusive=True``, which asserts every kind is
    present — the mixed step always carries both bodies). The
    decomposition must be UNIQUE: zero solutions means the traced body
    changed under the caller's constants, several means the marker
    algebra cannot attribute sites to kinds — both raise ValueError
    rather than fabricate a launch count.
    """
    import itertools

    markers = len(_MARKER_RE.findall(program_text))
    budget = markers - int(overhead_markers)
    names = sorted(kinds)
    L = int(num_layers)
    cand = (1, L) if exclusive else (0, 1, L)
    solutions = []
    for combo in itertools.product(cand, repeat=len(names)):
        if sum(s * int(kinds[n]) for s, n in zip(combo, names)) == budget:
            if combo not in solutions:
                solutions.append(combo)
    if len(solutions) != 1:
        why = "no assignment matches" if not solutions else \
            f"{len(solutions)} assignments match"
        raise ValueError(
            f"mixed_launch_stats: {markers} rsqrt markers do not "
            f"decompose as {overhead_markers} overhead + per-kind body "
            f"sites in {cand} for kinds {dict(kinds)} ({why}) — the "
            f"traced body changed; re-derive the marker constants")
    sites = dict(zip(names, solutions[0]))
    total = sum(sites.values())
    return {
        "marker_count": markers,
        "sites": sites,
        "total_body_sites": total,
        "num_layers": L,
        "launches_per_token": total / float(tokens_per_invocation),
        "collapsed": all(s <= 1 for s in sites.values()),
    }


__all__ = ["fusion_stats", "instruction_metadata", "launch_stats",
           "mixed_launch_stats", "shape_bytes", "strip_metadata"]
