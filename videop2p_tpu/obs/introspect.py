"""Compiled-program cost/memory introspection.

XLA's own analyses of a compiled executable are deterministic and available
on EVERY backend — including CPU, where the TPU may be down (the round-4/5
failure class that left whole rounds evidence-free). This module mines a
jitted program's lowered/compiled artifact for:

  * ``cost_analysis()`` — flops, bytes accessed, transcendentals: what the
    optimized program *computes*, independent of wall-clock health;
  * ``memory_analysis()`` — argument/output/temp/generated-code bytes,
    folded into a ``peak_hbm_bytes`` estimate (arguments + outputs + temps +
    generated code − aliased/donated bytes) that the run_videop2p HBM gate
    and the ledger's ``memory`` snapshots can check predicted-vs-actual
    against;
  * a stable optimized-HLO fingerprint (sha256 of the HLO text with the
    nondeterministic ``metadata={...}`` annotations stripped) — two runs of
    the same program produce the same fingerprint, and a *changed*
    fingerprint marks "XLA built a different program" across runs;
  * an instruction-category histogram of the optimized HLO (fusion / dot /
    convolution / custom-call / copy counts — the op-family view of a
    device trace, but available without hardware).

Everything is emitted as one flat ``program_analysis`` record
(:func:`analysis_record` keys are schema-stable — ``obs/history.py`` keys
its regression rules on them). All entry points degrade to ``None`` rather
than raise: introspection must never take a run down.
"""

from __future__ import annotations

import hashlib
import re
from typing import Any, Dict, Optional

import jax

from videop2p_tpu.obs.spans import span

__all__ = [
    "analyze_compiled",
    "analyze_jitted",
    "compile_abstract",
    "hlo_fingerprint",
    "instruction_histogram",
    "tpu_custom_call_counts",
    "abstractify_args",
    "PROGRAM_METRICS",
]

# the numeric metric keys a program_analysis record carries (history rules
# reference these names; keep in sync with analyze_compiled)
PROGRAM_METRICS = (
    "flops",
    "transcendentals",
    "bytes_accessed",
    "argument_bytes",
    "output_bytes",
    "temp_bytes",
    "alias_bytes",
    "generated_code_bytes",
    "peak_hbm_bytes",
    "hlo_instructions",
)

_METADATA_RE = re.compile(r",?\s*metadata=\{[^}]*\}")
# the module-level source-location tables the compiler prints ahead of the
# computations (`FileNames` / `FunctionNames` / `FileLocations` /
# `StackFrames`, each a header line then numbered rows) — where the program
# was traced from, never what it computes
_LOCATION_TABLE_RE = re.compile(
    r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n(?:\d+ .*\n)*",
    re.MULTILINE,
)
# a Pallas TPU kernel's custom call, with the op_name of its metadata
_TPU_KERNEL_RE = re.compile(
    r'custom_call_target="tpu_custom_call"[^\n]*?op_name="([^"]*)"'
)
# one optimized-HLO instruction: `%name = type[...] opcode(...`
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*\S+\s+([\w\-]+)\(",
                       re.MULTILINE)


def hlo_fingerprint(hlo_text: str) -> str:
    """Stable 16-hex-char fingerprint of an optimized-HLO module.

    ``metadata={...}`` annotations (op names, stack-frame ids) and the
    module's source-location tables are the only part of the text that
    varies with how the program was traced rather than what it computes —
    strip them, hash the rest. Same program → same
    fingerprint across processes; a changed fingerprint across runs means
    XLA built a structurally different executable.
    """
    return hashlib.sha256(
        _METADATA_RE.sub("", _LOCATION_TABLE_RE.sub("", hlo_text)).encode()
    ).hexdigest()[:16]


def tpu_custom_call_counts(hlo_text: str) -> Dict[str, int]:
    """Pallas TPU kernels in an optimized-HLO module, by kernel name:
    ``{"fused_group_norm": 12, ...}``. A kernel is a ``tpu_custom_call``
    custom call; its name is the ``pallas_call(name=...)`` scope ahead of
    ``/pallas_call`` in the instruction's ``op_name``. Static counts — a
    kernel inside a ``while`` body counts once. Empty off the TPU: this is
    how a run says whether it took the kernel branch or the XLA fallback."""
    counts: Dict[str, int] = {}
    for m in _TPU_KERNEL_RE.finditer(hlo_text):
        parts = m.group(1).split("/")
        if parts[-1].startswith("pallas_call"):
            parts = parts[:-1]
        name = parts[-1] if parts else "?"
        counts[name] = counts.get(name, 0) + 1
    return dict(sorted(counts.items()))


def instruction_histogram(hlo_text: str) -> Dict[str, int]:
    """Optimized-HLO instruction counts by opcode (fusion, dot, copy, ...),
    sorted descending so the dominant categories lead the record."""
    counts: Dict[str, int] = {}
    for m in _INSTR_RE.finditer(hlo_text):
        op = m.group(1)
        counts[op] = counts.get(op, 0) + 1
    return dict(sorted(counts.items(), key=lambda kv: (-kv[1], kv[0])))


def _num(v) -> float:
    """Cost-analysis values arrive as floats; keep integral ones as ints so
    the JSONL record (and its diffs) read naturally."""
    f = float(v)
    return int(f) if f == int(f) else f


def analyze_compiled(compiled, hlo_text: Optional[str] = None
                     ) -> Dict[str, Any]:
    """Mine one ``jax.stages.Compiled`` executable into a flat record.

    Each constituent analysis is independently guarded: a backend that
    cannot produce one of them (e.g. no ``as_text`` on some plugin
    runtimes) yields a record missing those keys, not an exception.
    ``hlo_text`` is ``compiled.as_text()`` where the caller already holds
    it: printing a UNet-scale module takes seconds, so the ledger's
    analysis prints it once for this record and the ``comm_analysis`` one.

    Under an active ledger each part is a span (``analysis.cost``,
    ``analysis.memory``, ``analysis.mine``: the regular expressions and the
    hash), children of the open ``program.analysis`` beside the ledger's
    own ``analysis.text`` and ``analysis.comm``.

    Conventions: flops/bytes are
    XLA's STATIC per-module counts — ``while``/``scan`` trip counts are
    not multiplied in — and the memory analysis describes the analyzed
    backend's schedule. Both are deterministic for a given program and
    backend, which is the property the cross-run diff needs; neither is a
    wall-clock predictor.
    """
    rec: Dict[str, Any] = {}
    try:
        with span("analysis.cost"):
            cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        rec["flops"] = _num(cost.get("flops", 0.0))
        rec["transcendentals"] = _num(cost.get("transcendentals", 0.0))
        rec["bytes_accessed"] = _num(cost.get("bytes accessed", 0.0))
    except Exception:  # noqa: BLE001 — introspection is best-effort
        pass
    try:
        with span("analysis.memory"):
            mem = compiled.memory_analysis()
        arg = int(mem.argument_size_in_bytes)
        out = int(mem.output_size_in_bytes)
        tmp = int(mem.temp_size_in_bytes)
        alias = int(mem.alias_size_in_bytes)
        code = int(mem.generated_code_size_in_bytes)
        rec.update(
            argument_bytes=arg,
            output_bytes=out,
            temp_bytes=tmp,
            alias_bytes=alias,
            generated_code_bytes=code,
            # aliased (donated) bytes are counted in both arguments and
            # outputs but occupy HBM once — subtract one copy
            peak_hbm_bytes=arg + out + tmp + code - alias,
        )
    except Exception:  # noqa: BLE001
        pass
    try:
        text = compiled.as_text() if hlo_text is None else hlo_text
        with span("analysis.mine"):
            hist = instruction_histogram(text)
            rec["hlo_fingerprint"] = hlo_fingerprint(text)
            rec["hlo_instructions"] = sum(hist.values())
            rec["hlo_histogram"] = hist
            kernels = tpu_custom_call_counts(text)
            if kernels:  # CPU records keep their pinned schema
                rec["tpu_custom_calls"] = kernels
    except Exception:  # noqa: BLE001
        pass
    return rec


def abstractify_args(args, kwargs):
    """Array leaves → ShapeDtypeStructs (so a later ``.lower()`` never
    touches possibly-donated/deleted buffers); everything else unchanged.

    The abstraction must be EXACT: it carries everything of a leaf that jit
    keys a trace and a lowering on, so that ``lower(...).compile()`` at the
    abstract arguments is the signature of the call they were taken from
    and is served by jax's own trace, lowering and executable caches — the
    program the call built, not a second trace, lowering and load of it:

      * ``weak_type`` (``jnp.asarray(0)``, the ``TrainState.step`` of every
        tune, stays weak through a scan carry): part of the aval the trace
        is keyed on;
      * the sharding of a COMMITTED leaf, on one device or many: the call
        lowers with it as the argument's ``in_sharding``, and a
        ShapeDtypeStruct that has a sharding counts as committed. So a
        sharded call analyses the partitioned SPMD program it executed
        (the sharded ``program_analysis`` and ``comm_analysis`` events). An
        uncommitted leaf lowers with no sharding, and gets none here.

    Layouts are not carried: nothing in this package sets one."""

    def to_abstract(leaf):
        if isinstance(leaf, jax.ShapeDtypeStruct):
            return leaf
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            sharding = None
            if (getattr(leaf, "_committed", True)
                    and not isinstance(leaf, jax.core.Tracer)):
                sharding = getattr(leaf, "sharding", None)
            return jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, sharding=sharding,
                weak_type=bool(getattr(leaf, "weak_type", False)),
            )
        return leaf

    return (jax.tree.map(to_abstract, args),
            jax.tree.map(to_abstract, kwargs))


def compile_abstract(jitted, *args, **kwargs):
    """Lower + compile ``jitted`` at the given (possibly abstract) arguments
    and return the ``jax.stages.Compiled`` executable, or None on failure.

    This is the ahead-of-time path (``jit(f).lower(...).compile()``) — the
    executable is built but NEVER executed, which is what makes the whole
    analysis CPU-runnable while the accelerator is down. At the exact
    signature of a call ``jitted`` has already served
    (:func:`abstractify_args` of its arguments) nothing is built: jax
    returns the trace, the lowering and the executable that call made, from
    its own in-memory caches. At any other signature — one weak type or one
    commitment off — it is a full trace, lowering and backend compile (a
    load from the persistent cache where one is configured).
    """
    try:
        return jitted.lower(*args, **kwargs).compile()
    except Exception:  # noqa: BLE001
        return None


def analyze_jitted(jitted, *args, **kwargs) -> Optional[Dict[str, Any]]:
    """:func:`compile_abstract` + :func:`analyze_compiled`, or None on any
    failure."""
    compiled = compile_abstract(jitted, *args, **kwargs)
    return analyze_compiled(compiled) if compiled is not None else None
