"""The device this process runs on: the accelerator check the chip
entry points (chip_smoke.py, bench.py) start with, and the placement of
JAX's persistent compilation cache.

Every dispatch in the package keys on ``jax.default_backend()``: a
process that finds no chip would otherwise train on the CPU and exit 0.
``require_tpu`` is the one place that turns "no accelerator" into an
error, for the callers whose numbers only mean something on the chip.

The backend's start (on a TPU, the runtime's: seconds) happens under
the first ``jax.devices()`` of the process. ``start_backend`` times it
as the root span ``runtime/backend``; the program's first device
touches call it, so the span holds the start whichever path the user
took.
"""

from __future__ import annotations

import os
from typing import Any, Dict

# device kinds every Pallas kernel family has been through the Mosaic
# compiler for (CHANGES.md, PR 22). A kind missing here is an error, not
# a default: VMEM budgets and tilings are per generation.
KNOWN_TPU_KINDS = ("TPU v5 lite",)

# fixed, git-ignored, inside the checkout: the directory is part of the
# cache key's environment, so a path that moves (tempfile, pid) never hits
_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


_backend_seen = False


def start_backend() -> None:
    """Start JAX's backend under the root span ``runtime/backend``
    {devices}, if no backend is up yet; every later call does nothing.
    A backend that something else started first is not timed."""
    global _backend_seen
    if _backend_seen:
        return
    _backend_seen = True
    import jax
    from jax._src import xla_bridge     # no public way to ask
    if xla_bridge.backends_are_initialized():
        return
    from .profiler import count, span
    with span("runtime/backend"):
        count(devices=len(jax.devices()))


def device_info() -> Dict[str, Any]:
    """The device as JAX reports it (initializes the backend)."""
    import jax
    start_backend()
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu() -> Dict[str, Any]:
    """``device_info()`` of a known TPU, or RuntimeError."""
    info = device_info()
    if info["platform"] != "tpu":
        raise RuntimeError(
            f"a TPU is required, JAX found platform={info['platform']!r} "
            f"({info['kind']!r} x{info['count']}); refusing to fall back")
    if info["kind"] not in KNOWN_TPU_KINDS:
        raise RuntimeError(
            f"unknown TPU device_kind {info['kind']!r}; the Pallas kernels "
            f"were compiled for {KNOWN_TPU_KINDS} only")
    return info


def configure_compile_cache() -> None:
    """Place the persistent compilation cache (called once, at package
    import; touches no backend). ``JAX_COMPILATION_CACHE_DIR`` wins and
    JAX reads it itself — nothing is set in code then; otherwise the
    cache lands in ``.jax_cache/`` at the checkout root. The
    min-compile-time write threshold (1 s by default; the entry-size one
    is already 0) drops to zero either way, so the serving scorer ladder
    (sub-second compiles) is kept next to the scan chunk.

    What keys an entry is the program's lowered text, so every array a
    jitted function closes over is part of the key. The trainer's
    programs therefore take the dataset's facts as ARGUMENTS
    (models/gbdt.py ``_build_jit_fns``, ops/bucketize.py
    ``_bin_rows_jit``): their key is the shapes, ``GrowConfig`` and the
    ``None``-pattern of ``FeatureMeta``, and a new dataset of a known
    shape loads the compiled scan chunk from here instead of compiling
    it (10 s on a v5e). A closure over an array derived from the data
    makes every dataset miss: tests/test_compile_reuse.py holds the
    lowered texts of two seeds equal, and the ``cache_hits`` /
    ``cache_misses`` counts on the program's spans (runtime/profiler.py)
    say on the chip whether an entry was found.

    The key also holds every Pallas kernel's Mosaic body as bytecode,
    and JAX strips no location from it: by default each operation
    carries the absolute file and line of up to 10 Python frames above
    it (the trainer's, the grower's, the kernel's). An edit anywhere up
    the call stack, or another checkout path, would then miss every
    kernel's entry. ``jax_traceback_in_locations_limit`` 0 lowers every
    operation without its Python frames (unless
    ``JAX_TRACEBACK_IN_LOCATIONS_LIMIT`` says otherwise), so a kernel's
    key is its code and shapes alone (tests/test_histogram_pallas.py).
    This holds in every process; op names in a device trace keep their
    scopes.

    A process held to the CPU (``JAX_PLATFORMS=cpu``: the test suite,
    the virtual-mesh dryrun) gets no persistent cache: XLA:CPU's
    loader logs a machine-feature mismatch error on every cache hit
    (jaxlib 0.9.0), and CPU compiles are not what the cache is for."""
    import jax
    if "JAX_TRACEBACK_IN_LOCATIONS_LIMIT" not in os.environ:
        jax.config.update("jax_traceback_in_locations_limit", 0)
    if (jax.config.jax_platforms or "").split(",")[0] == "cpu":
        return
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
