"""What every cell shares: finding its files by name, the device, the
compile cache, compile counting and the result line."""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"


class SpecError(ValueError):
    """A cell names a file that is not there or is malformed."""


# --------------------------------------------------------------------------
# specs, found by name
# --------------------------------------------------------------------------
def _json(kind: str, name: str) -> dict:
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise SpecError(f"no {kind[:-1]} named {name!r} ({path})")
    return {**json.loads(path.read_text()), "name": name}


def config(name: str) -> dict:
    cfg = _json("configs", name)
    if not (BENCH / "references" / f"{cfg['reference']}.py").is_file():
        raise SpecError(f"config {name!r}: no reference {cfg['reference']!r}")
    return cfg


def traffic(name: str) -> dict:
    t = _json("traffic", name)
    if not (BENCH / "drivers" / f"{t['kind']}.py").is_file():
        raise SpecError(f"traffic {name!r}: no driver {t['kind']!r}")
    return t


def workload(name: str) -> dict:
    """The cell with its configuration and traffic resolved."""
    w = _json("workloads", name)
    w["config"], w["traffic"] = config(w["config"]), traffic(w["traffic"])
    for m in w["end_to_end"] + w["per_layer"]:
        if not (BENCH / "metrics" / f"{m}.py").is_file():
            raise SpecError(f"workload {name!r}: no metric reader {m!r}")
    return w


def workload_names() -> list[str]:
    return sorted(p.stem for p in (BENCH / "workloads").glob("*.json"))


def _load_file(path: Path, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric(name: str):
    """The reader module of metric ``name``: ``UNIT`` and ``read(rec)``."""
    return _load_file(BENCH / "metrics" / f"{name}.py",
                      f"bench_metric_{name.replace('.', '_')}")


def driver(kind: str):
    return importlib.import_module(f"bench.drivers.{kind}")


def reference(name: str):
    return importlib.import_module(f"bench.references.{name}")


def model_config(cfg: dict, **overrides):
    """The program's ModelConfig built from a config file's fields."""
    from repro.configs.base import ModelConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {k: v for k, v in cfg.items() if k in fields}
    kw["name"] = cfg["name"]
    return ModelConfig(**{**kw, **overrides})


# --------------------------------------------------------------------------
# device and compilation
# --------------------------------------------------------------------------
def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(n_devices: int) -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:n_devices])


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path in the checkout, unless
    ``JAX_COMPILATION_CACHE_DIR`` names one.  Every program is kept, however
    fast it compiled, so that a second run compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Backend compiles and persistent-cache hits, as JAX reports them."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def fresh(self) -> int:
        """Compiles that did not come from the persistent cache."""
        return self.compiles - self.cache_hits


# --------------------------------------------------------------------------
# result
# --------------------------------------------------------------------------
def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, compared: dict,
                breakdown: dict | None = None, extra: dict | None = None
                ) -> str:
    """The last line of standard output.  ``compared`` comes last: each
    number that decided ``correct`` beside its limit."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out.update(extra or {})
    out["compared"] = compared
    return json.dumps(out)


def print_compared(compared: dict) -> None:
    """Each compared number beside its limit, as the last lines of stderr."""
    for name, c in compared.items():
        print(f"{name} {c['value']!r} limit {c['limit']!r} "
              f"({'ok' if c['ok'] else 'FAIL'})", file=sys.stderr, flush=True)


def compare(name: str, value: float, limit: float) -> dict:
    """A number that may not exceed its limit (NaN fails)."""
    return {name: {"value": float(value), "limit": float(limit),
                   "ok": bool(value <= limit)}}
