"""Distributed observability: collective-communication accounting and
per-device telemetry for sharded runs.

The PR-2/3/4 obs stack was single-process-blind at the distributed layer:
``instrumented_jit`` silently skipped program analysis when arguments were
sharded, and the mesh/ring machinery (``parallel/mesh.py``,
``parallel/ring.py``) emitted zero events. This module closes that gap
along the two axes Megatron-LM-style comm accounting and GSPMD sharding
introspection cover (PAPERS.md):

  * **Collective accounting** — :func:`collective_summary` classifies the
    collective instructions of an optimized-HLO module (all-reduce /
    all-gather / reduce-scatter / collective-permute / all-to-all) with
    per-kind counts and byte volumes; :func:`comm_analysis_record` folds
    that plus the per-arg/out sharding specs and the partition count into
    one flat ``comm_analysis`` ledger event. ``instrumented_jit`` emits it
    on every cache miss of a sharded program — the ring-attention
    ``ppermute`` chain and the Megatron psum pairing become measured,
    regression-gated quantities (``obs/history.py COMM_RULES``).

    Conventions (same as the PR-3 cost analysis): counts and bytes are
    STATIC per-module quantities — a collective inside a ``scan`` body
    counts once, not per trip — and bytes are the result-shape bytes of
    each collective instruction (async ``-start``/``-done`` pairs count
    once, at the start). Deterministic for a given program and backend,
    which is what the cross-run diff needs; not a wire-traffic meter.

  * **Per-device telemetry + divergence** — :func:`make_device_probe`
    builds a shard_map probe that rides the fused edit scan exactly like
    :func:`~videop2p_tpu.obs.telemetry.latent_stats` (fixed shapes, zero
    extra dispatches, off by default): per-device abs-max/mean/NaN/inf of
    each device's LOCAL shard, plus a cross-replica divergence scalar —
    the max abs difference of the probed tensor across the mesh axes it
    is supposed to be REPLICATED over. :func:`replica_divergence` is the
    standalone form (the dryrun applies it to the trained params across
    the ``data`` axis — the data-parallel invariant). Divergence must be
    0.0: the regression rule has a zero noise floor.

Pure stdlib+numpy+jax (the obs import contract, pinned in
tests/test_ledger_schema.py).
"""

from __future__ import annotations

import re
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from videop2p_tpu.obs.telemetry import latent_stats

__all__ = [
    "COLLECTIVE_KINDS",
    "COMM_ANALYSIS_FIELDS",
    "DEVICE_TELEMETRY_FIELDS",
    "collective_summary",
    "comm_analysis_record",
    "sharding_strs",
    "make_device_probe",
    "replica_divergence",
    "tree_replica_divergence",
    "split_device_stats",
    "summarize_device_stats",
]

# the collective op families XLA's SPMD partitioner emits (async forms
# appear as <kind>-start/<kind>-done pairs and count once)
COLLECTIVE_KINDS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
    "collective-broadcast",
)

# schema-stable field sets (test_ledger_schema pins them): every
# comm_analysis / device_telemetry ledger event carries at least these
COMM_ANALYSIS_FIELDS = (
    "num_partitions",
    "collective_count",
    "collective_bytes",
    "per_kind",
    "arg_shardings",
    "out_shardings",
    "hlo_fingerprint",
)
DEVICE_TELEMETRY_FIELDS = (
    "devices",
    "divergence_max",
    "divergence_final",
    "per_device_abs_max_peak",
    "per_device_nan_total",
)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "f8e5m2fnuz": 1, "f8e4m3fnuz": 1, "f8e4m3b11fnuz": 1, "f8e3m4": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

# `f32[2,8,16]` result-shape literals (layout braces carry no brackets,
# so they never match); empty dims = scalar
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
# one HLO instruction line: `%name = <result-type> opcode(...`
_INSTR_HEAD_RE = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*)$")
_COLL_OP_RE = re.compile(
    r"\s(" + "|".join(COLLECTIVE_KINDS) + r")(-start|-done)?\("
)
_PARTITIONS_RE = re.compile(r"num_partitions\s*=\s*(\d+)")


def _shape_bytes(type_str: str) -> int:
    """Total bytes of every `dtype[dims]` literal in an HLO result type
    (tuple types sum their components; unknown dtypes contribute 0)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        size = _DTYPE_BYTES.get(dtype)
        if size is None:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += size * n
    return total


def collective_summary(hlo_text: str) -> Dict[str, Any]:
    """Classify an optimized-HLO module's collective instructions.

    Returns ``{"collective_count", "collective_bytes", "per_kind"}`` where
    ``per_kind`` maps each present kind to ``{"count", "bytes"}``. Bytes
    are the result-shape bytes of each instruction; ``-done`` halves of
    async pairs are skipped so a start/done pair counts once.
    """
    per_kind: Dict[str, Dict[str, int]] = {}
    for line in hlo_text.splitlines():
        head = _INSTR_HEAD_RE.match(line)
        if head is None:
            continue
        m = _COLL_OP_RE.search(" " + head.group(1))
        if m is None or m.group(2) == "-done":
            continue
        kind = m.group(1)
        # result type = everything left of the opcode token
        nbytes = _shape_bytes(head.group(1)[: max(m.start() - 1, 0)])
        slot = per_kind.setdefault(kind, {"count": 0, "bytes": 0})
        slot["count"] += 1
        slot["bytes"] += nbytes
    return {
        "collective_count": sum(s["count"] for s in per_kind.values()),
        "collective_bytes": sum(s["bytes"] for s in per_kind.values()),
        "per_kind": per_kind,
    }


def sharding_strs(shardings) -> List[str]:
    """Compact human/JSON-friendly rendering of a sharding sequence:
    NamedShardings render as their PartitionSpec, anything else as its
    (truncated) str."""
    out = []
    for s in shardings or ():
        spec = getattr(s, "spec", None)
        out.append(str(spec) if spec is not None else str(s)[:120])
    return out


def comm_analysis_record(compiled, hlo_text: Optional[str] = None
                         ) -> Optional[Dict[str, Any]]:
    """Mine one ``jax.stages.Compiled`` executable into a flat
    ``comm_analysis`` record: partition count, per-kind collective
    counts/bytes (plus flattened ``<kind>_count``/``<kind>_bytes`` keys
    the regression rules can target), and the per-arg/out sharding specs.
    ``hlo_text`` is ``compiled.as_text()`` where the caller already holds it.
    Returns None when the module text is unavailable."""
    from videop2p_tpu.obs.introspect import hlo_fingerprint

    text = hlo_text
    if text is None:
        try:
            text = compiled.as_text()
        except Exception:  # noqa: BLE001 — introspection is best-effort
            return None
    rec: Dict[str, Any] = dict(collective_summary(text))
    # the HloModule header (first line) carries num_partitions; its
    # entry_computation_layout can run to tens of KBs for a UNet-sized
    # program, so scan the whole line, not a fixed prefix
    m = _PARTITIONS_RE.search(text.split("\n", 1)[0])
    rec["num_partitions"] = int(m.group(1)) if m else 1
    rec["hlo_fingerprint"] = hlo_fingerprint(text)
    for kind, slot in rec["per_kind"].items():
        flat = kind.replace("-", "_")
        rec[f"{flat}_count"] = slot["count"]
        rec[f"{flat}_bytes"] = slot["bytes"]
    try:
        in_sh = compiled.input_shardings
        args_sh = in_sh[0] if isinstance(in_sh, tuple) else in_sh
        rec["arg_shardings"] = sharding_strs(args_sh)
    except Exception:  # noqa: BLE001
        rec["arg_shardings"] = []
    try:
        out_sh = compiled.output_shardings
        rec["out_shardings"] = sharding_strs(
            jax.tree.leaves(out_sh)
            if not isinstance(out_sh, (list, tuple))
            else out_sh
        )
    except Exception:  # noqa: BLE001
        rec["out_shardings"] = []
    return rec


# --------------------------------------------------------------- probes --


def _spec_axes(spec) -> Tuple[str, ...]:
    """Mesh axis names a PartitionSpec shards over."""
    axes: List[str] = []
    for part in tuple(spec or ()):
        if part is None:
            continue
        axes.extend(part if isinstance(part, tuple) else (part,))
    return tuple(axes)


def _shard_map(fn, mesh, in_specs, out_specs):
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def make_device_probe(
    mesh,
    *,
    latent_spec=None,
    divergence_axes: Optional[Sequence[str]] = None,
) -> Callable:
    """Per-device telemetry probe for tensors inside a jitted program over
    ``mesh``.

    Returns ``probe(x) -> dict`` of fixed-shape arrays suitable for a scan
    ``ys`` (the :func:`~videop2p_tpu.pipelines.sampling.edit_sample`
    ``device_probe=`` seam): ``device_abs_max`` / ``device_mean`` /
    ``device_nan_count`` / ``device_inf_count`` of each device's LOCAL
    shard, each of shape ``(mesh.size,)`` in mesh-coordinate order
    (``probe.device_ids`` maps index → device id), plus ``divergence`` —
    the max abs difference of ``x`` across ``divergence_axes``.

    ``latent_spec`` is the PartitionSpec the probed tensor is expected to
    carry (default ``P("data", "frames")`` — the repo's latent
    convention); ``divergence_axes`` defaults to every >1-sized mesh axis
    the spec does NOT shard over — the axes along which the tensor must be
    replicated, so any nonzero divergence means the replicas disagree.
    When no such axis exists the divergence channel is a constant 0.0.
    """
    from jax.sharding import PartitionSpec as P

    axis_names = tuple(mesh.axis_names)
    spec = latent_spec if latent_spec is not None else P("data", "frames")
    if divergence_axes is None:
        used = set(_spec_axes(spec))
        divergence_axes = tuple(
            a for a in axis_names if a not in used and mesh.shape[a] > 1
        )
    else:
        divergence_axes = tuple(divergence_axes)

    def body(x_local):
        out = {
            f"device_{k}": jax.lax.all_gather(v, axis_names)
            for k, v in latent_stats(x_local).items()
        }
        if divergence_axes:
            g = jax.lax.all_gather(x_local.astype(jnp.float32), divergence_axes)
            div = jnp.max(jnp.abs(g - g[:1]))
            # identical on every device, so the replicated out_spec is honest
            div = jax.lax.pmax(div, axis_names)
        else:
            div = jnp.zeros((), jnp.float32)
        out["divergence"] = div
        return out

    def probe(x):
        out = _shard_map(body, mesh, in_specs=(spec,), out_specs=P())(x)
        # all_gather over the full axis tuple stacks one leading axis of
        # size mesh.size; flatten defensively in case of nested gathers
        return {
            k: (v if v.ndim == 0 else v.reshape(-1)) for k, v in out.items()
        }

    probe.device_ids = [int(d.id) for d in mesh.devices.flat]
    probe.divergence_axes = divergence_axes
    return probe


def replica_divergence(
    x,
    mesh,
    *,
    axes: Sequence[str],
    spec=None,
) -> jax.Array:
    """Max abs cross-replica difference of ``x`` along mesh ``axes`` it is
    supposed to be replicated over — 0.0 iff every replica holds identical
    values (the data-parallel invariant for params after a train step).

    ``spec`` is the PartitionSpec of ``x`` over the REMAINING axes
    (default: fully replicated — sharded inputs are gathered first, which
    is correct but not free)."""
    from jax.sharding import PartitionSpec as P

    axes = tuple(axes)
    spec = spec if spec is not None else P()
    if not axes:
        return jnp.zeros((), jnp.float32)

    def body(x_local):
        g = jax.lax.all_gather(x_local.astype(jnp.float32), axes)
        return jax.lax.pmax(
            jnp.max(jnp.abs(g - g[:1])), tuple(mesh.axis_names)
        )

    return _shard_map(body, mesh, in_specs=(spec,), out_specs=P())(x)


def tree_replica_divergence(tree, mesh, *, axes: Sequence[str]) -> jax.Array:
    """Worst-case :func:`replica_divergence` over a pytree's array leaves
    (callers with big trees should pass a representative sub-tree — each
    leaf is its own shard_map program)."""
    leaves = [
        l for l in jax.tree.leaves(tree)
        if hasattr(l, "shape") and getattr(l, "size", 0)
    ]
    if not leaves or not tuple(axes):
        return jnp.zeros((), jnp.float32)
    return jnp.max(
        jnp.stack([replica_divergence(l, mesh, axes=axes) for l in leaves])
    )


# ------------------------------------------------------------- decoders --


def split_device_stats(stats: Dict) -> Tuple[Dict, Dict]:
    """Split a telemetry tree into (plain per-step stats, device-probe
    channels) — the ledger writes them as separate events."""
    dev = {
        k: v for k, v in stats.items()
        if k.startswith("device_") or k == "divergence"
    }
    rest = {k: v for k, v in stats.items() if k not in dev}
    return rest, dev


def summarize_device_stats(
    stats: Dict, device_ids: Optional[Sequence[int]] = None
) -> Dict[str, Any]:
    """Ledger-sized summary of the device-probe channels: per-device
    abs-max peaks and NaN/inf totals over the step axis, plus the
    divergence extremes. Degenerate inputs summarize to zeros rather than
    raising (a killed run's partial stats must still land)."""
    host = {k: np.asarray(v, np.float64) for k, v in stats.items()}
    rec: Dict[str, Any] = {}

    def per_device(key):
        v = host.get(key)
        if v is None or v.size == 0:
            return None
        return v.reshape(-1, v.shape[-1]) if v.ndim > 1 else v[None]

    am = per_device("device_abs_max")
    rec["devices"] = int(am.shape[-1]) if am is not None else 0
    rec["per_device_abs_max_peak"] = (
        [round(float(v), 6) for v in am.max(axis=0)] if am is not None else []
    )
    mean = per_device("device_mean")
    if mean is not None:
        rec["per_device_mean_final"] = [
            round(float(v), 6) for v in mean[-1]
        ]
    for key, out in (("device_nan_count", "per_device_nan_total"),
                     ("device_inf_count", "per_device_inf_total")):
        v = per_device(key)
        rec[out] = [int(t) for t in v.sum(axis=0)] if v is not None else []
    rec["nan_total"] = int(sum(rec["per_device_nan_total"]))
    dv = host.get("divergence")
    if dv is not None and dv.size:
        flat = dv.reshape(-1)
        rec["divergence_max"] = float(flat.max())
        rec["divergence_final"] = float(flat[-1])
    else:
        rec["divergence_max"] = 0.0
        rec["divergence_final"] = 0.0
    if device_ids is not None:
        rec["device_ids"] = [int(i) for i in device_ids]
    return rec
