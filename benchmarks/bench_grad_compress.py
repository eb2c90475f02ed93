"""Beyond-paper: error-feedback gradient compression — convergence and
wire-traffic reduction on a small LM (the paper's Stage I/II applied to
distributed-training traffic; DESIGN.md §2)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data import DataConfig, synthetic_batch
from repro.models import build_model, reduced_for_smoke
from repro.models import nn as rnn
from repro.optim import AdamWConfig, GradCompressConfig
from repro.runtime.steps import init_opt_state, make_train_step
from .common import csv_row


def _train(compress: bool, steps: int = 40):
    cfg = reduced_for_smoke(get_config("smollm-360m")).scaled(n_layers=2)
    model = build_model(cfg)
    params = rnn.init_tree(model.desc(), jax.random.key(0))
    gc = GradCompressConfig(eb_rel=1e-3) if compress else None
    opt = init_opt_state(params, gc)
    step_fn = jax.jit(make_train_step(model, AdamWConfig(lr=1e-3, total_steps=steps), gc))
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=128, global_batch=4)
    losses, wire = [], []
    for s in range(steps):
        batch = {k: jnp.asarray(v) for k, v in synthetic_batch(dcfg, s).items()}
        params, opt, m = step_fn(params, opt, batch)
        losses.append(float(m["loss"]))
        if "wire_bits_per_value" in m:
            wire.append(float(m["wire_bits_per_value"]))
    return losses, wire


def run(steps: int = 40):
    base, _ = _train(False, steps)
    comp, wire = _train(True, steps)
    rows = [csv_row("variant", "loss_start", "loss_end", "wire_bits_per_value",
                    "traffic_reduction_x")]
    rows.append(csv_row("fp32_grads", f"{base[0]:.4f}", f"{np.mean(base[-5:]):.4f}", 32.0, 1.0))
    wb = float(np.mean(wire))
    rows.append(csv_row("eb_quantized_ef", f"{comp[0]:.4f}", f"{np.mean(comp[-5:]):.4f}",
                        f"{wb:.2f}", f"{32.0 / wb:.1f}"))
    return rows


def main() -> None:
    for r in run():
        print(r)


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    main()
