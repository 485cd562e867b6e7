"""Run manifests: what environment produced a performance number.

A single wall-clock is meaningless without provenance — the ROADMAP's
fabric-DSE sweeps and the future TPU column can only be compared against
numbers whose producing environment is on record.  :func:`capture`
collects that record once per process (git SHA, python/jax/jaxlib
versions, platform + device kind, CPU count, XLA-compilation-cache
cold/warm state) and every performance artifact embeds it:

* ``results/BENCH_*.json`` carry a top-level ``manifest`` block
  (validated by ``results/check_bench.py`` — a BENCH file without one
  fails the gate);
* Chrome traces written by :meth:`repro.obs.trace.Tracer.write_chrome`
  carry it under ``metadata.manifest``;
* ``ExploreRecord`` jsonl files start with a manifest header line
  (skipped transparently by ``repro.explore.from_jsonl``).

Capture is deterministic modulo the environment fields themselves: two
captures in one process (or on one machine at one commit) are equal,
except ``xla_cache`` which reflects the cache directory's state at call
time — pass ``refresh=True`` to re-inspect.
"""

from __future__ import annotations

import dataclasses
import os
import platform
import subprocess
import sys
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

__all__ = ["MANIFEST_SCHEMA", "RunManifest", "capture", "validate_manifest"]

#: bump on any field add/rename/retype; validators reject other versions
MANIFEST_SCHEMA = 1

#: legal xla_cache states: "off" (no cache dir in use), "cold" (dir in use
#: but absent/empty at capture time), "warm" (dir has entries)
XLA_CACHE_STATES = ("off", "cold", "warm")


@dataclass(frozen=True)
class RunManifest:
    """The environment fingerprint embedded in every perf artifact."""

    schema: int
    git_sha: str          # full SHA, or "unknown" outside a checkout
    python: str           # e.g. "3.10.13"
    jax: str              # jax.__version__, or "unavailable"
    jaxlib: str
    platform: str         # platform.platform()
    device_kind: str      # jax.devices()[0].device_kind, e.g. "cpu"/"TPU v4"
    backend: str          # jax.default_backend()
    cpu_count: int
    xla_cache: str        # "off" | "cold" | "warm"

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "RunManifest":
        errors = validate_manifest(d)
        if errors:
            raise ValueError(f"invalid manifest: {'; '.join(errors)}")
        return RunManifest(**d)


def _git_sha() -> str:
    """Full commit SHA: CI env var first, then the checkout, else unknown."""
    sha = os.environ.get("GITHUB_SHA")
    if sha:
        return sha
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=10, cwd=os.path.dirname(os.path.abspath(__file__)))
        if out.returncode == 0:
            return out.stdout.strip()
    except Exception:
        pass
    return "unknown"


def _jax_fields() -> Dict[str, str]:
    try:
        import jax
        import jaxlib
        dev = jax.devices()[0]
        return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "device_kind": getattr(dev, "device_kind", str(dev)),
                "backend": jax.default_backend()}
    except Exception:           # pragma: no cover - jax is baked in
        return {"jax": "unavailable", "jaxlib": "unavailable",
                "device_kind": "unavailable", "backend": "unavailable"}


def _xla_cache_state() -> str:
    """State of the cache directory JAX actually uses: the
    ``jax_compilation_cache_dir`` setting, which ``JAX_COMPILATION_CACHE_DIR``
    or :func:`repro.compile_cache.use_compile_cache` set."""
    import jax
    cache_dir = jax.config.jax_compilation_cache_dir
    if not cache_dir:
        return "off"
    try:
        return "warm" if os.listdir(cache_dir) else "cold"
    except OSError:
        return "cold"


_CACHED: Optional[RunManifest] = None


def capture(refresh: bool = False) -> RunManifest:
    """The process's run manifest (captured once, then cached).

    ``refresh=True`` re-inspects the mutable fields (the XLA cache state
    can flip cold -> warm mid-process); everything else is stable for the
    life of the process by construction.
    """
    global _CACHED
    if _CACHED is None or refresh:
        _CACHED = RunManifest(
            schema=MANIFEST_SCHEMA,
            git_sha=_git_sha(),
            python=platform.python_version(),
            platform=platform.platform(),
            cpu_count=os.cpu_count() or 1,
            xla_cache=_xla_cache_state(),
            **_jax_fields())
    return _CACHED


def validate_manifest(d: Any) -> List[str]:
    """Structural validation shared by regress/history; mirrors the
    stdlib-only copy in ``results/check_bench.py`` (kept separate so the
    gate never needs ``repro`` importable)."""
    errors: List[str] = []
    if not isinstance(d, dict):
        return [f"manifest is {type(d).__name__}, expected a dict"]
    fields = {f.name for f in dataclasses.fields(RunManifest)}
    for name in sorted(fields - set(d)):
        errors.append(f"manifest missing field {name!r}")
    for name in sorted(set(d) - fields):
        errors.append(f"manifest has unknown field {name!r}")
    if d.get("schema") != MANIFEST_SCHEMA:
        errors.append(f"manifest schema {d.get('schema')!r}, expected "
                      f"{MANIFEST_SCHEMA}")
    if "cpu_count" in d and (not isinstance(d["cpu_count"], int)
                             or d["cpu_count"] < 1):
        errors.append(f"manifest cpu_count={d['cpu_count']!r}, expected a "
                      f"positive int")
    if "xla_cache" in d and d["xla_cache"] not in XLA_CACHE_STATES:
        errors.append(f"manifest xla_cache={d['xla_cache']!r}, expected one "
                      f"of {XLA_CACHE_STATES}")
    for name in fields - {"schema", "cpu_count"}:
        if name in d and not isinstance(d[name], str):
            errors.append(f"manifest {name}={d[name]!r}, expected a string")
    return errors
