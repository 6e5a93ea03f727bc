"""Structured emission (DESIGN.md §15): schema-versioned JSONL + manifests.

Every training / evaluation / fleet entry point can be handed a
:class:`MetricWriter`; records are append-only JSON objects, one per line,
stamped ``{"schema": "repro-obs/1", "kind": <kind>, ...}`` and validated
against the per-kind required-field table at write time — schema drift
fails at the producer, not in a downstream parser.  A run log always
starts with a ``manifest`` record (:func:`run_manifest`: config hash,
seed, git sha, jax/device info), the contract :func:`validate_jsonl`
enforces (CLI: ``python -m repro.obs.validate``).
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import platform
import subprocess
import sys
import time

import numpy as np

SCHEMA = "repro-obs/1"

# Required fields per record kind (beyond "schema"/"kind").  Extra fields
# are always allowed — the schema pins the floor, not the ceiling.
REQUIRED_FIELDS = {
    "manifest": ("run_id", "created_unix", "jax", "backend", "device_kind",
                 "cfg_hash"),
    "train_chunk": ("episode", "episodes", "wall_s", "stats"),
    "eval": ("metrics",),
    "fleet_frame": ("frame", "p50_s", "p95_s", "p99_s", "drop_rate",
                    "slo_viol_rate", "mean_backlog_s"),
    "fleet_summary": ("metrics",),
}


def _jsonable(x):
    """Map arrays / np scalars / dataclasses to plain JSON values."""
    if isinstance(x, dict):
        return {str(k): _jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_jsonable(v) for v in x]
    if isinstance(x, (str, bool, int, float)) or x is None:
        return x
    if isinstance(x, (np.bool_,)):
        return bool(x)
    if isinstance(x, np.integer):
        return int(x)
    if isinstance(x, np.floating):
        return float(x)
    if hasattr(x, "tolist"):            # np / jnp arrays (and 0-d scalars)
        return _jsonable(np.asarray(x).tolist())
    if dataclasses.is_dataclass(x) and not isinstance(x, type):
        return repr(x)
    return str(x)


# public name for downstream consumers (benchmarks.common.save_json)
to_jsonable = _jsonable


def cfg_hash(cfg) -> str:
    """Short stable hash of a frozen-dataclass config (its repr includes
    every field, nested configs included)."""
    return hashlib.sha256(repr(cfg).encode()).hexdigest()[:16]


def _git_sha():
    try:
        here = os.path.dirname(os.path.abspath(__file__))
        out = subprocess.run(["git", "rev-parse", "HEAD"], cwd=here,
                             capture_output=True, text=True, timeout=5)
        sha = out.stdout.strip()
        return sha if out.returncode == 0 and sha else None
    except Exception:
        return None


def run_manifest(cfg=None, extra=None) -> dict:
    """The shared run-manifest record (DESIGN.md §15): reproducibility
    context — git sha, jax/jaxlib versions, device kind/count, config
    hash + repr, seed — stamped into every JSONL run log and (via
    ``benchmarks.common.save_json``) every benchmark JSON."""
    import jax                                    # deferred: keep the
    try:                                          # writer importable early
        import jaxlib
        jaxlib_v = getattr(jaxlib, "__version__", None)
    except Exception:
        jaxlib_v = None
    dev = jax.devices()[0]
    rec = {
        "schema": SCHEMA,
        "kind": "manifest",
        "run_id": f"{int(time.time() * 1e3):x}-{os.getpid():x}",
        "created_unix": time.time(),
        "argv": list(sys.argv),
        "python": platform.python_version(),
        "jax": jax.__version__,
        "jaxlib": jaxlib_v,
        "backend": jax.default_backend(),
        "device_kind": dev.device_kind,
        "device_count": jax.device_count(),
        "cpu_count": os.cpu_count(),
        "git_sha": _git_sha(),
        "cfg_hash": cfg_hash(cfg) if cfg is not None else None,
    }
    if cfg is not None:
        rec["cfg"] = repr(cfg)
        rec["seed"] = getattr(cfg, "seed", None)
    if extra:
        rec.update(_jsonable(extra))
    return rec


def progress_line(episode: int, last: dict) -> str:
    """The human-readable per-chunk progress line (the console sink of the
    structured logger) — byte-identical to the legacy ``train_t2drl``
    print format."""
    return (f"ep {episode:4d} reward {last['episode_reward']:9.2f} "
            f"hit {last['hit_ratio']:.3f} "
            f"G {last['utility']:7.2f}")


def validate_record(rec) -> None:
    """Raise ``ValueError`` unless ``rec`` is a schema-valid record."""
    if not isinstance(rec, dict):
        raise ValueError(f"record must be a JSON object, got {type(rec)}")
    if rec.get("schema") != SCHEMA:
        raise ValueError(f"unknown schema {rec.get('schema')!r}; "
                         f"expected {SCHEMA!r}")
    kind = rec.get("kind")
    if kind not in REQUIRED_FIELDS:
        raise ValueError(f"unknown record kind {kind!r}; expected one of "
                         f"{sorted(REQUIRED_FIELDS)}")
    missing = [f for f in REQUIRED_FIELDS[kind] if f not in rec]
    if missing:
        raise ValueError(f"{kind!r} record is missing required fields "
                         f"{missing}")


def validate_jsonl(path) -> int:
    """Validate a JSONL run log: every line a schema-valid record, the
    first a ``manifest``.  Returns the record count; raises ``ValueError``
    (with the offending line number) on any violation."""
    n = 0
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                raise ValueError(f"{path}:{lineno}: invalid JSON: {e}")
            try:
                validate_record(rec)
            except ValueError as e:
                raise ValueError(f"{path}:{lineno}: {e}")
            if n == 0 and rec["kind"] != "manifest":
                raise ValueError(f"{path}:{lineno}: first record must be a "
                                 f"manifest, got {rec['kind']!r}")
            n += 1
    if n == 0:
        raise ValueError(f"{path}: empty run log")
    return n


class MetricWriter:
    """Append-only schema-versioned JSONL sink.

    Records are validated at write time and flushed per line (crash-safe
    logs).  ``ensure_manifest`` makes "manifest first" idempotent across
    nested callers — e.g. a benchmark opens the writer and stamps the
    manifest, then hands it to ``train_t2drl``, whose own
    ``ensure_manifest`` becomes a no-op."""

    def __init__(self, path, *, mode: str = "w"):
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        self.path = path
        self._f = open(path, mode)
        self._wrote_manifest = False

    def write(self, kind: str, **fields) -> dict:
        rec = {"schema": SCHEMA, "kind": kind}
        rec.update(_jsonable(fields))
        validate_record(rec)
        self._f.write(json.dumps(rec) + "\n")
        self._f.flush()
        return rec

    def manifest(self, cfg=None, extra=None) -> dict:
        rec = run_manifest(cfg=cfg, extra=extra)
        validate_record(rec)
        self._f.write(json.dumps(_jsonable(rec)) + "\n")
        self._f.flush()
        self._wrote_manifest = True
        return rec

    def ensure_manifest(self, cfg=None, extra=None):
        if not self._wrote_manifest:
            self.manifest(cfg=cfg, extra=extra)

    def close(self):
        if not self._f.closed:
            self._f.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
