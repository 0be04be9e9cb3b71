"""Persistent XLA compilation-cache wiring.

The flagship round program takes about 40 s to compile on XLA:CPU -- about
one full CPU round -- and ~47 s for a v5e.  A warm persistent cache amortises
that across benchmark runs, tier-1 test sessions and repeated experiments, so
the fed entry drivers, ``tests/conftest.py``, ``chip_smoke.py`` and
``benchmark/run.py`` all route through here.

The default cache dir is fingerprinted by the host CPU's feature flags:
XLA:CPU AOT entries embed machine features, and loading a cache written on
a different host risks SIGILL mid-run (observed: ``cpu_aot_loader.cc``
feature-mismatch errors when this box was reprovisioned between rounds).
An operator-set ``JAX_COMPILATION_CACHE_DIR`` always wins, and nothing else
in the repo names a cache directory: every entry point calls
:func:`enable_persistent_cache` and sets no path of its own.

Reading an XLA:CPU entry back logs two ``cpu_aot_loader.cc`` errors per
executable -- "Target machine feature +prefer-no-scatter / +prefer-no-gather
is not supported on the host machine" -- even for entries this very host
wrote into its own fingerprinted directory.  Those two are LLVM tuning
pseudo-features that XLA:CPU adds at compile time, not CPUID bits, so the
loader's host check can never find them; every real feature in the list
matches, the entries load, and the loaded programs give the same results as
fresh compiles (the tier-1 suite runs on cache reads).  The flood is
harmless and is the compiler's to fix; the fingerprint cannot key it away,
because there is no host on which those two "features" would match.  None of
this touches the TPU: its entries carry no machine-feature list.
"""

from __future__ import annotations

import hashlib
import os
import sys
from contextlib import contextmanager
from typing import Optional


def cache_fingerprint() -> str:
    """8-hex digest of the host CPU's feature flags (empty flags on
    non-procfs platforms hash to a stable constant)."""
    try:
        with open("/proc/cpuinfo") as f:
            flags = next((l for l in f if l.startswith("flags")), "")
    except OSError:
        flags = ""
    return hashlib.sha1(flags.encode()).hexdigest()[:8]


def default_cache_dir(root: Optional[str] = None) -> str:
    """``<repo>/.jax_cache/<cpu-fingerprint>`` (root defaults to the
    directory containing the ``heterofl_tpu`` package)."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    return os.path.join(root, ".jax_cache", cache_fingerprint())


def key_scope_version() -> str:
    """Fold ``obs.trace.SCOPE_VERSION`` into every persistent-cache key and
    return the string folded in.

    The cache key is computed from the program with its debug info stripped
    (``jax_compilation_cache_include_metadata_in_key`` is off, and has to
    stay off: line numbers are metadata, so every edit would recompile
    every program).  ``jax.named_scope`` names ARE metadata: a program that
    differs from a cached one only by a scope gets the cached executable,
    whose device events carry the old names (measured on the v5e, PERF.md
    PR 26).  jax's key has one slot for the embedding program,
    ``cache_key.custom_hook``; the scope vocabulary's version goes there,
    so a bump of the version misses the cache once and nothing else ever
    does."""
    from jax._src import cache_key

    from ..obs.trace import SCOPE_VERSION

    tag = f"heterofl_tpu.scopes.v{SCOPE_VERSION}"
    if not hasattr(cache_key, "custom_hook"):  # a jax without the slot
        import warnings

        warnings.warn("jax's compile-cache key has no custom_hook: a cached "
                      "program may carry stale scope names (PERF.md, PR 26)")
        return ""
    cache_key.custom_hook = lambda: tag
    return tag


def enable_persistent_cache(path: Optional[str] = None) -> str:
    """Point jax at a persistent compilation cache and return the dir.

    Safe to call before or after ``import jax``: the env var covers a
    not-yet-imported jax (and any child processes), and a live config
    update covers an already-imported one.  Every key of the cache carries
    the scope vocabulary's version (:func:`key_scope_version`), and every
    compilation from here on is a span of the process's record
    (``obs/spans.py``; installed once, a second call registers nothing).
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or path or default_cache_dir()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", path)
    os.makedirs(path, exist_ok=True)
    if "jax" in sys.modules:
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    key_scope_version()
    from ..obs import spans

    spans.install()
    return path


@contextmanager
def no_persistent_cache():
    """Compile fresh (no persistent-cache reads OR writes) for the scope.

    The chaos drill (ISSUE 15) runs kill -> resume cycles inside ONE
    process; resuming with programs *deserialized* from a warm persistent
    cache while the killed run's donated buffers are still being reclaimed
    trips the XLA:CPU serialized-executable donation bug this repo already
    priced in for codec programs (MEASUREMENTS.md Round 10): bitwise-
    nondeterministic params on a stable subset of leaves, fresh compiles
    always correct (reproduced 3/4 warm vs 5/5 clean cold on the drill's
    corruption-fallback plan).  The drill therefore compiles its small
    synthetic programs fresh; everything outside the scope keeps the warm
    cache, so the tier-1 gate's cache contract is untouched.

    The config flag alone is NOT enough: ``compilation_cache.
    is_cache_used`` latches its decision in module globals at the first
    compile, so in a process that already compiled with the cache on
    (pytest under conftest's warm cache) a later flag flip is silently
    ignored -- ``reset_cache()`` drops the latch (and the initialized
    cache object) so the flag is re-read inside and after the scope."""
    import jax

    from jax.experimental.compilation_cache.compilation_cache import reset_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        reset_cache()
