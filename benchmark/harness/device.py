"""Device checks and the few process-wide settings the harness makes: chip
visibility, the compile cache's path, and nothing else of the program's."""
import glob
import os

PLATFORM = "tpu"     # a measurement path that finds no chip fails


class DeviceError(Exception):
    """No accelerator, or fewer chips than the cell asks for."""


def restrict_visible_chips(chips):
    """A one-chip cell on a host with more chips sees one (the variables
    heturun binds a worker with, PERF.md PR 21). Must run before jax is
    imported. Chips are counted from the device nodes."""
    have = len(glob.glob("/dev/vfio/[0-9]*"))
    if chips == 1 and have > 1:
        os.environ.setdefault("TPU_VISIBLE_CHIPS", "0")
        os.environ.setdefault("TPU_CHIPS_PER_PROCESS_BOUNDS", "1,1,1")
        os.environ.setdefault("TPU_PROCESS_BOUNDS", "1,1,1")


def use_compile_cache(bench_dir):
    """jax's persistent cache at a fixed path inside the checkout, unless
    JAX_COMPILATION_CACHE_DIR names one (jax reads that itself)."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(bench_dir, ".cache", "jax")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def require(chips):
    """The devices the cell runs on, or DeviceError naming what was found."""
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != PLATFORM:
        raise DeviceError(
            f"jax platform is {d.platform!r} ({d.device_kind}, "
            f"{len(devices)} device(s)), not {PLATFORM!r}: no result")
    if len(devices) < chips:
        raise DeviceError(
            f"the cell asks for {chips} chip(s), jax sees {len(devices)} "
            f"{d.device_kind}: no result")
    return devices[:chips]


def describe(devices):
    import jax
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def memory_peak_bytes(devices):
    """Peak HBM in use on the fullest chip, from the allocator's own
    counters, or None where the backend reports none. On the TPU
    `peak_bytes_in_use` counts live buffers (arguments, results) and NOT
    the scratch a running program takes, which the runtime reserves apart
    and reports as `peak_bytes_reserved`: a BERT-base step at 64 sequences
    reads 1.43 GB in use and 2.79 GB reserved, and the Wide&Deep step's
    reservation equals its compiled scratch to 0.004 % (PERF.md, PR 22).
    The peak is their sum."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"])
                         + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks) if peaks else None


class CompileCounter:
    """Compile requests as jax reports them (copy of
    chip_smoke._CompileCounter): every compilation asks the persistent cache
    first, so hits + misses counts the programs built in an interval."""

    def __init__(self):
        from jax import monitoring
        self.hits = self.misses = 0
        monitoring.register_event_listener(self._on_event)

    def _on_event(self, name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    @property
    def requests(self):
        return self.hits + self.misses
