"""Central flag / environment-variable registry.

Ref: `nd4j/nd4j-common/src/main/java/org/nd4j/config/ND4JSystemProperties.java`
(115 lines) and `ND4JEnvironmentVars.java` (122 lines) — the reference
declares every tunable system property / env var in one place with
javadoc, instead of scattering `System.getenv` calls. Same discipline
here: every environment variable this framework reads is declared below
with a type, default, and description. Modules import :data:`flags`
(the singleton) instead of touching ``os.environ`` directly.

TPU note: JAX/XLA's own flags (``XLA_FLAGS``, ``JAX_PLATFORMS``…) are
owned by JAX; they are *documented* here when the framework's tests or
tools set them, but reads go through JAX itself.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional


def _as_bool(s: str) -> bool:
    return s.strip().lower() in ("1", "true", "yes", "on")


@dataclass(frozen=True)
class Flag:
    """One declared environment variable (ref: the per-constant javadoc
    blocks in ND4JSystemProperties)."""
    name: str
    default: Any
    parse: Callable[[str], Any]
    doc: str

    def get(self) -> Any:
        raw = os.environ.get(self.name)
        if raw is None or raw == "":
            return self.default
        try:
            return self.parse(raw)
        except (ValueError, TypeError):
            return self.default


class FlagRegistry:
    """The registry. Attribute access returns the *current* parsed value
    (env re-read each time, like the reference's System.getProperty use),
    so tests can monkeypatch os.environ."""

    def __init__(self):
        self._flags: Dict[str, Flag] = {}

    def declare(self, attr: str, name: str, default: Any,
                parse: Callable[[str], Any], doc: str) -> None:
        self._flags[attr] = Flag(name, default, parse, doc)

    def __getattr__(self, attr: str) -> Any:
        flags = object.__getattribute__(self, "_flags")
        if attr in flags:
            return flags[attr].get()
        raise AttributeError(attr)

    def env_name(self, attr: str) -> str:
        return self._flags[attr].name

    def describe(self) -> str:
        """Human-readable table of every declared flag (ref: the javadoc
        surface of ND4JSystemProperties)."""
        lines = []
        for attr, f in sorted(self._flags.items()):
            cur = f.get()
            lines.append(f"{f.name} (flags.{attr})")
            lines.append(f"    default={f.default!r} current={cur!r}")
            lines.append(f"    {f.doc}")
        return "\n".join(lines)

    def as_dict(self) -> Dict[str, Any]:
        return {attr: f.get() for attr, f in self._flags.items()}


flags = FlagRegistry()

# -- data locations (ref: ND4JSystemProperties.ND4J_RESOURCES_CACHE_DIR) --
flags.declare(
    "data_dir", "DL4J_TPU_DATA_DIR", os.path.expanduser("~/.deeplearning4j_tpu"),
    str, "Root directory for downloaded/cached datasets and fixtures.")
flags.declare(
    "mnist_dir", "MNIST_DATA_DIR", "", str,
    "Directory holding the 4 MNIST idx files (raw or .gz). Empty = probe "
    "standard locations, then fall back to the labeled synthetic set.")
flags.declare(
    "cifar10_dir", "CIFAR10_DATA_DIR", "", str,
    "Directory holding CIFAR-10 binary batches. Empty = probe standard "
    "locations, then fall back to the labeled synthetic set.")

# -- dtype / precision (ref: ND4JSystemProperties.DTYPE) ------------------
flags.declare(
    "dtype", "DL4J_TPU_DTYPE", "float32", str,
    "Default network dtype for newly built configurations: float32 | "
    "bfloat16. bfloat16 = mixed precision (bf16 compute on the MXU, "
    "f32 master params/updater state/loss).")

# -- kernels --------------------------------------------------------------
flags.declare(
    "flash_attention", "DL4J_TPU_FLASH_ATTENTION", True, _as_bool,
    "Allow the Pallas flash-attention kernel where it wins (TPU, long "
    "sequences). false = always use plain fused XLA attention.")
flags.declare(
    "flash_min_seq", "DL4J_TPU_FLASH_MIN_SEQ", 1024, int,
    "Minimum sequence length at which implementation='auto' selects the "
    "Pallas flash kernel on TPU (not yet read on the chip: PERF.md, "
    "section 7, row 9).")

# -- profiler / debugging (ref: OpExecutioner.ProfilingMode) --------------
flags.declare(
    "profiling_mode", "DL4J_TPU_PROFILING_MODE", "", str,
    "Global default profiling mode: '' | nan_panic | inf_panic | "
    "any_panic | operations. Mirrors profiler.ProfilerConfig modes.")
flags.declare(
    "verbose", "DL4J_TPU_VERBOSE", False, _as_bool,
    "Verbose runtime logging (ref: libnd4j Environment verbose flag).")

# -- native runtime -------------------------------------------------------
flags.declare(
    "native_lib", "DL4J_TPU_NATIVE_LIB", "", str,
    "Path to the prebuilt native runtime shared object. Empty = build "
    "on demand from native/ (falls back to pure numpy on failure).")
flags.declare(
    "native_disable", "DL4J_TPU_NATIVE_DISABLE", False, _as_bool,
    "Force the pure-numpy fallback even if the native runtime builds.")

# -- UI / serving ---------------------------------------------------------
flags.declare(
    "ui_port", "DL4J_TPU_UI_PORT", 9000, int,
    "Default port for the training UI stats server (ref: PlayUIServer "
    "org.deeplearning4j.ui.port).")
