"""Batched serving driver: prefill + greedy decode with KV cache.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --smoke \
      --batch 4 --prompt-len 64 --gen 32

Continuous mode (`--continuous`) runs the compression-aware serving tier
(DESIGN.md §9) instead: a `ContinuousBatcher` with the paged KV pool under
synthetic Poisson arrivals — long-context requests resolve to a
`Policy.fixed_ratio` byte budget for compress-on-evict, short ones stay raw.

  PYTHONPATH=src python -m repro.launch.serve --smoke --continuous
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.decision_cache import DecisionCache
from repro.core.policy import serving_policies
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import make_device_mesh
from repro.models import build_model, reduced_for_smoke
from repro.models import nn as rnn
from repro.runtime import sharding
from repro.runtime.batcher import ContinuousBatcher, Request
from repro.runtime.steps import make_decode_step, make_prefill_step


def run_continuous(args, cfg, model, params) -> dict:
    """Continuous serving under Poisson arrivals (arrival clock = decode
    steps). Prompt lengths mix short and long around `--long-threshold`
    so both PolicySet arms (raw / fixed_ratio) are exercised."""
    rng = np.random.default_rng(0)
    max_len = args.prompt_len + args.gen
    decisions = DecisionCache()
    b = ContinuousBatcher(
        model, params, slots=args.slots, max_len=max_len, eos_id=-1,
        page_tokens=args.page_tokens, arena_pages=args.arena_pages,
        policies=serving_policies(args.target_ratio),
        long_threshold=args.long_threshold, decisions=decisions,
    )
    if not b.paged:
        raise SystemExit(f"--continuous needs the paged KV pool; {args.arch} "
                         "does not support it (MLA / quantized KV)")
    short_len = max(4, args.prompt_len // 4)
    reqs = [
        Request(
            rid=i,
            prompt=rng.integers(
                1, cfg.vocab, args.prompt_len if i % 2 else short_len
            ).astype(np.int32),
            max_new=args.gen,
        )
        for i in range(args.requests)
    ]
    arrive = np.cumsum(rng.exponential(1.0 / args.rate, size=len(reqs)))
    t0 = time.time()
    clock, nxt_req, steps, decoded = 0.0, 0, 0, 0
    pending: list[Request] = []
    peak_resident = 0
    while nxt_req < len(reqs) or pending or b.preempted or b.live.any():
        while nxt_req < len(reqs) and arrive[nxt_req] <= clock:
            pending.append(reqs[nxt_req])
            nxt_req += 1
        while b.preempted and b.try_admit(b.preempted[0]):
            b.preempted.pop(0)
        while pending and b.try_admit(pending[0]):
            pending.pop(0)
        if b.live.any():
            decoded += int(b.live.sum())
            b.step()
            steps += 1
        peak_resident = max(peak_resident, b.resident_kv_bytes())
        clock += 1.0
    wall = time.time() - t0
    done = sum(r.done for r in reqs)
    out = {
        "completed": done,
        "steps": steps,
        "decode_tok_s": decoded / max(wall, 1e-9),
        "evictions": b.stats["evictions"],
        "restores": b.stats["restores"],
        "page_reuses": b.stats["page_reuses"],
        "peak_resident_kv_bytes": peak_resident,
        "decision_hits": decisions.hits,
    }
    print(f"[serve --continuous] {done}/{len(reqs)} requests in {steps} "
          f"decode steps ({out['decode_tok_s']:.1f} tok/s); "
          f"evictions {out['evictions']}, restores {out['restores']}, "
          f"page reuses {out['page_reuses']}, "
          f"peak resident KV {peak_resident / 1e6:.2f} MB, "
          f"decision-cache hits {decisions.hits}")
    assert done == len(reqs), f"continuous serving dropped {len(reqs) - done}"
    return out


def main(argv=None) -> dict:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--sample", action="store_true")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous serving: paged KV pool + Poisson arrivals")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--rate", type=float, default=0.25,
                    help="Poisson arrival rate (requests per decode step)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--page-tokens", type=int, default=16)
    ap.add_argument("--arena-pages", type=int, default=None)
    ap.add_argument("--target-ratio", type=float, default=8.0)
    ap.add_argument("--long-threshold", type=int, default=64)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced_for_smoke(cfg)
    model = build_model(cfg)
    params = rnn.init_tree(model.desc(), jax.random.key(0))
    if args.continuous:
        return run_continuous(args, cfg, model, params)
    mesh = make_device_mesh()

    rng = np.random.default_rng(0)
    b = args.batch
    prompts = jnp.asarray(rng.integers(1, cfg.vocab, (b, args.prompt_len)), jnp.int32)
    max_len = args.prompt_len + args.gen
    batch = {"tokens": prompts}
    if cfg.frontend == "vision":
        batch["patch_embeds"] = jnp.asarray(
            rng.standard_normal((b, cfg.frontend_len, cfg.d_model)), jnp.float32
        )
    if cfg.encdec:
        batch["frames"] = jnp.asarray(
            rng.standard_normal((b, cfg.frontend_len, cfg.d_model)), jnp.float32
        )

    prefill = make_prefill_step(model)
    decode = make_decode_step(model, sample=args.sample)
    with sharding.activate(mesh, sharding.SERVE_RULES):
        cache = model.init_cache(b, max_len)
        t0 = time.time()
        logits, cache = jax.jit(prefill)(params, batch, cache)
        nxt = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        t_prefill = time.time() - t0
        jit_decode = jax.jit(decode)
        toks = [nxt]
        key = jax.random.key(1)
        t0 = time.time()
        for i in range(args.gen - 1):
            key, sub = jax.random.split(key)
            if args.sample:
                nxt, cache = jit_decode(params, nxt, cache, sub)
            else:
                nxt, cache = jit_decode(params, nxt, cache)
            toks.append(nxt)
        jax.block_until_ready(nxt)
        t_decode = time.time() - t0
    out = jnp.concatenate(toks, axis=1)
    tput = b * (args.gen - 1) / max(t_decode, 1e-9)
    print(f"[serve] prefill {args.prompt_len} toks x{b}: {t_prefill:.2f}s; "
          f"decode {args.gen - 1} steps: {t_decode:.2f}s ({tput:.1f} tok/s)")
    print("[serve] sample output ids:", np.asarray(out[0, :16]))
    return {"tokens": np.asarray(out), "prefill_s": t_prefill, "decode_s": t_decode}


if __name__ == "__main__":
    main()
