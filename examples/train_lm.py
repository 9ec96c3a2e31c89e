"""End-to-end training driver: a ~100M-parameter qwen3-family model on the
streaming pipeline with the SW-AKDE drift monitor, checkpointing, and
resume — the full trainer stack at laptop scale.

Run: PYTHONPATH=src python examples/train_lm.py --steps 200
(CPU note: ~100M params trains a few steps/min; --d-model 128 for a fast demo)
"""
import argparse
import dataclasses

import jax

from repro.compile_cache import use_compile_cache
from repro.configs import registry
from repro.data.pipeline import DataConfig
from repro.train.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--optimizer", default="adamw",
                    choices=("adamw", "adam8bit", "adafactor"))
    args = ap.parse_args()

    cfg = dataclasses.replace(
        registry.get_config("qwen3-4b"),
        n_layers=args.layers, d_model=args.d_model,
        n_heads=8, n_kv_heads=4, head_dim=args.d_model // 8,
        d_ff=4 * args.d_model, vocab=32_000, seq_parallel=False)
    n_params = cfg.param_count()
    print(f"model: {args.layers}L d={args.d_model} ~{n_params/1e6:.0f}M params "
          f"(qwen3 family: GQA + qk-norm)")

    data_cfg = DataConfig(vocab=cfg.vocab, batch=args.batch, seq=args.seq)
    tcfg = TrainerConfig(steps=args.steps, ckpt_every=50,
                         ckpt_dir=args.ckpt_dir, optimizer=args.optimizer,
                         lr=3e-4, log_every=10, monitor_drift=True)
    out = Trainer(cfg, data_cfg, tcfg).run(jax.random.PRNGKey(0))
    h = out["history"]
    print(f"loss: {h[0]['loss']:.3f} -> {h[-1]['loss']:.3f} over {len(h)} steps")
    drifts = [r for r in h if r.get("drift", {}).get("drift")]
    print(f"drift flags: {len(drifts)}; stragglers: "
          f"{sum(r['straggler'] for r in h)}")


if __name__ == "__main__":
    use_compile_cache()
    main()
