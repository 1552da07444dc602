"""Runtime kernel autotuning (reference: paddle/phi/kernels/autotune/ —
switch_autotune.h, cache.h, gpu_timer.h).

The reference times candidate algorithms (conv algos, transpose tilings) at
runtime and caches the winner per shape key. The TPU analog picks Pallas
kernel BLOCK CONFIGURATIONS: for a given (kernel, shape, dtype) key, each
candidate config is built, run, and timed with readback synchronization
(a value read back on the host closes the timing whatever the runtime's
``block_until_ready`` does), and the winner is cached in-process and
optionally on disk (the reference's autotune cache file).

Usage (how kernels/flash_attention consumes it)::

    tuner = get_autotuner()
    cfg = tuner.pick(
        key=("flash_attn", q.shape, str(q.dtype)),
        candidates=[{"block_q": 128, "block_k": 128},
                    {"block_q": 256, "block_k": 512}],
        build_fn=lambda cfg: (lambda: kernel_call(q, k, v, **cfg)),
    )
"""
from __future__ import annotations

import json
import os
import time

import numpy as np
import jax


def _measure(thunk, iters=3):
    """Median wall time of ``thunk`` with real readback sync."""
    out = thunk()
    np.asarray(jax.device_get(jax.tree.leaves(out)[0]))  # warmup + compile
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = thunk()
        np.asarray(jax.device_get(jax.tree.leaves(out)[0]))
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


class KernelAutotuner:
    """Per-key winner cache over measured candidate configs
    (reference: autotune/cache.h AlgorithmsCache)."""

    def __init__(self, cache_path=None, measure=_measure):
        self.cache: dict = {}
        self.measure = measure
        self.cache_path = cache_path or os.environ.get(
            "PADDLE_TPU_AUTOTUNE_CACHE")
        self.stats = {"hits": 0, "misses": 0}
        if self.cache_path and os.path.exists(self.cache_path):
            try:
                with open(self.cache_path) as f:
                    self.cache = {self._key(json.loads(k)): v
                                  for k, v in json.load(f).items()}
            except Exception:
                self.cache = {}

    @staticmethod
    def _key(key):
        return tuple(tuple(k) if isinstance(k, (list, tuple)) else k
                     for k in key)

    def pick(self, key, candidates, build_fn, iters=3):
        """Return the fastest candidate config for ``key`` (cached).

        build_fn(cfg) -> zero-arg thunk running the kernel at that config;
        a candidate whose build/run raises is skipped (invalid tilings are
        expected in the search space, matching the reference's failure-
        tolerant algo search).
        """
        from ..core.flags import GLOBAL_FLAGS
        k = self._key(key)
        if k in self.cache:
            self.stats["hits"] += 1
            return self.cache[k]
        self.stats["misses"] += 1
        # measured repeats per candidate: FLAGS_cudnn_exhaustive_search_times
        # (the reference's exhaustive-search iteration knob; <=0 = default)
        flag_iters = int(GLOBAL_FLAGS.get("cudnn_exhaustive_search_times"))
        if flag_iters > 0:
            iters = flag_iters
        best_cfg, best_t = None, None
        for cfg in candidates:
            try:
                t = self.measure(build_fn(cfg), iters=iters)
            except Exception:
                continue
            if best_t is None or t < best_t:
                best_cfg, best_t = cfg, t
        if best_cfg is None:
            raise RuntimeError(
                f"kernel autotune: every candidate failed for key {key}")
        self.cache[k] = best_cfg
        # bounded winner cache (FLAGS_search_cache_max_number): evict
        # oldest entries (dict preserves insertion order)
        bound = max(int(GLOBAL_FLAGS.get("search_cache_max_number")), 1)
        while len(self.cache) > bound:
            self.cache.pop(next(iter(self.cache)))
        self._persist()
        return best_cfg

    def _persist(self):
        if not self.cache_path:
            return
        try:
            with open(self.cache_path, "w") as f:
                json.dump({json.dumps(list(k)): v
                           for k, v in self.cache.items()}, f)
        except Exception:
            pass


_global: KernelAutotuner | None = None


def get_autotuner() -> KernelAutotuner:
    global _global
    if _global is None:
        _global = KernelAutotuner()
    return _global


def autotune_enabled() -> bool:
    """Gate (reference: switch_autotune.h EnableAutotune): opt-in via env —
    measurement costs a few kernel launches per new shape key."""
    return os.environ.get("PADDLE_TPU_AUTOTUNE") == "1"


def pick_cached(key, requested, candidates, build_fn, traced=False):
    """The shared winner-cache discipline every Pallas kernel consumes
    (flash_attention, rms_norm, fused_adamw): a cached winner always wins;
    under a trace only the cache is consulted — measurement needs concrete
    buffers — so ``requested`` rides through unmeasured; otherwise the
    caller's explicit config competes against ``candidates`` and the
    measured winner is cached. Returns the chosen config dict."""
    tuner = get_autotuner()
    cached = tuner.cache.get(tuner._key(key))
    if cached is not None:
        return cached
    if traced:
        return requested
    cands = list(candidates)
    if requested not in cands:
        cands.insert(0, requested)
    return tuner.pick(key=key, candidates=cands, build_fn=build_fn)


__all__ = ["KernelAutotuner", "get_autotuner", "autotune_enabled",
           "pick_cached"]
