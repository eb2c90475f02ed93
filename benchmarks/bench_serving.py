"""Serving-tier benchmark + parity smoke (DESIGN.md §9).

Two questions about the compression-aware paged KV pool
(`runtime/batcher.py`):

* **Parity** — under page pressure (tiny arena, forced compress-on-evict /
  decompress-on-hit cycles) with `Policy.raw`, does every request decode
  the EXACT token stream of a pressure-free run (huge arena, no
  evictions)? Raw page round-trips are bit-identical by construction, so
  any token mismatch means the pool corrupted a page. This feeds the
  bench gate's absolute `serving_page_parity` check, together with a
  direct byte-level round-trip probe over bf16 page stacks.

* **Compression** — under a saturation workload where every request is
  long-context (resolves to `Policy.fixed_ratio`), how many bytes does
  the evicted-page store hold vs. the same schedule at `Policy.raw`, and
  what does the compression work cost in decode throughput? Reported
  (`store_ratio`, `tok_s_ratio`), not gated — wall times are
  machine-relative.

    PYTHONPATH=src python -m benchmarks.bench_serving
"""

from __future__ import annotations

import time

import numpy as np


def _setup():
    import jax

    from repro.configs import get_config
    from repro.models import build_model, reduced_for_smoke
    from repro.models import nn as rnn

    cfg = reduced_for_smoke(get_config("smollm-360m")).scaled(n_layers=2)
    model = build_model(cfg)
    params = rnn.init_tree(model.desc(), jax.random.key(0))
    return cfg, model, params


def _workload(cfg, n: int, prompt_len: int, max_new: int, seed: int = 0):
    from repro.runtime.batcher import Request

    rng = np.random.default_rng(seed)
    return [
        Request(
            rid=i,
            prompt=rng.integers(1, cfg.vocab, prompt_len).astype(np.int32),
            max_new=max_new,
        )
        for i in range(n)
    ]


def _drive(b, reqs):
    """batcher.run with per-step sampling of the evicted-page store."""
    pending = list(reqs)
    it, peak_store = 0, 0
    t0 = time.perf_counter()
    while (pending or b.preempted or b.live.any()) and it < 10_000:
        while b.preempted and b.try_admit(b.preempted[0]):
            b.preempted.pop(0)
        while pending and b.try_admit(pending[0]):
            pending.pop(0)
        b.step()
        store = sum(
            cp.nbytes for r in b.preempted for cp in r.page_comp.values()
        )
        peak_store = max(peak_store, store)
        it += 1
    return peak_store, time.perf_counter() - t0


def run_parity(n_requests: int = 4, prompt_len: int = 12, max_new: int = 20) -> dict:
    """Tiny-arena vs huge-arena paged serving at Policy.raw -> mismatches."""
    from repro.core.policy import Policy
    from repro.runtime import kvcomp
    from repro.runtime.batcher import ContinuousBatcher

    cfg, model, params = _setup()

    def one(arena_pages):
        b = ContinuousBatcher(
            model, params, slots=2, max_len=48, eos_id=-1,
            page_tokens=8, arena_pages=arena_pages, policies=Policy.raw(),
        )
        reqs = _workload(cfg, n_requests, prompt_len, max_new)
        b.run(reqs)
        return reqs, b

    ref, _ = one(arena_pages=None)  # never evicts
    cur, tiny = one(arena_pages=7)  # max_pages=6, forced evictions
    token_mismatches = sum(
        a.out != c.out or len(c.out) != max_new for a, c in zip(ref, cur)
    )
    # direct byte-level probe: raw page round-trips must be bit-identical
    rng = np.random.default_rng(7)
    byte_mismatches = 0
    for _ in range(4):
        page = rng.standard_normal((2, 8, 64)).astype("bfloat16")
        cp = kvcomp.compress_page(page, Policy.raw())
        back = kvcomp.decompress_page(cp)
        byte_mismatches += int(back.tobytes() != page.tobytes())
    return {
        "token_mismatches": int(token_mismatches),
        "byte_mismatches": int(byte_mismatches),
        "evictions": int(tiny.stats["evictions"]),
        "restores": int(tiny.stats["restores"]),
    }


def run_compression(
    n_requests: int = 6, prompt_len: int = 16, max_new: int = 24
) -> dict:
    """Saturation workload: fixed_ratio long-context policies vs raw at the
    same (tight) arena -> evicted-store byte ratio + decode tok/s ratio."""
    from repro.core.decision_cache import DecisionCache
    from repro.core.policy import Policy, serving_policies
    from repro.runtime.batcher import ContinuousBatcher

    cfg, model, params = _setup()

    def one(policies, decisions=None):
        b = ContinuousBatcher(
            model, params, slots=2, max_len=48, eos_id=-1,
            page_tokens=8, arena_pages=7, policies=policies,
            long_threshold=1, decisions=decisions,
        )
        reqs = _workload(cfg, n_requests, prompt_len, max_new)
        peak_store, wall = _drive(b, reqs)
        assert all(r.done for r in reqs)
        toks = sum(len(r.out) for r in reqs)
        return peak_store, toks / max(wall, 1e-9), b

    # warm the compression path's jit caches (fused kernel + ratio grid at
    # the page-stack shape) so the timed runs compare steady-state decode,
    # not first-call compiles
    from repro.runtime import kvcomp

    nl = cfg.n_layers
    dummy = np.zeros((nl, 8, cfg.n_kv_heads * cfg.dh), np.float32)
    dummy[0, 0, 0] = 1.0
    kvcomp.compress_page(dummy, serving_policies(8.0).resolve("kv/long/0"))

    decisions = DecisionCache()
    raw_store, raw_tok_s, _ = one(Policy.raw())
    comp_store, comp_tok_s, cb = one(serving_policies(8.0), decisions)
    return {
        "raw_peak_store_bytes": int(raw_store),
        "comp_peak_store_bytes": int(comp_store),
        "store_ratio": raw_store / max(comp_store, 1),
        "tok_s_ratio": comp_tok_s / max(raw_tok_s, 1e-9),
        "evictions": int(cb.stats["evictions"]),
        "decision_hits": int(decisions.hits),
    }


def run() -> dict:
    out = run_parity()
    out.update({f"compression_{k}": v for k, v in run_compression().items()})
    return out


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    r = run()
    for k, v in r.items():
        print(f"  {k}: {v}")
    ok = not r["token_mismatches"] and not r["byte_mismatches"] and r["evictions"]
    print("PASS" if ok else "FAIL")
    raise SystemExit(0 if ok else 1)
