"""End-to-end training driver: train a small LM on the synthetic corpus
with the LSH dedup stage enabled, checkpointing, and restart.

Defaults are CPU-sized (a ~5M-param model for a quick demo); pass
``--model-scale 100m --steps 300`` on real hardware for the full run.

Run:  PYTHONPATH=src python examples/train_lm.py --steps 30
"""
import argparse

from repro.compile_cache import enable_compile_cache
from repro.launch import train as train_mod
from repro.models.config import ModelConfig


SCALES = {
    # ~5M params: fast on 1 CPU core
    "5m": ModelConfig(name="lm-5m", n_layers=4, d_model=128, n_heads=4,
                      n_kv_heads=2, d_ff=512, vocab_size=2048,
                      attn_q_block=64, attn_kv_block=64, loss_seq_chunk=64,
                      param_dtype="float32", compute_dtype="float32",
                      remat="none"),
    # ~100M params: the assignment's end-to-end target (run on a real chip)
    "100m": ModelConfig(name="lm-100m", n_layers=12, d_model=768,
                        n_heads=12, n_kv_heads=4, d_ff=2048,
                        vocab_size=32768, attn_q_block=256,
                        attn_kv_block=256, loss_seq_chunk=256,
                        param_dtype="float32", compute_dtype="float32"),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model-scale", default="5m", choices=list(SCALES))
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = SCALES[args.model_scale]
    print(f"model: {cfg.name} ({cfg.param_count()/1e6:.1f}M params), "
          f"LSH dedup ON")

    # Reuse the production driver with our model config injected.
    orig = train_mod.build_model_config
    train_mod.build_model_config = lambda a: cfg
    try:
        argv = ["--steps", str(args.steps), "--batch", str(args.batch),
                "--seq", str(args.seq), "--ckpt-dir", args.ckpt_dir,
                "--ckpt-every", "10"]
        if args.resume:
            argv.append("--resume")
        result = train_mod.main(argv)
    finally:
        train_mod.build_model_config = orig
    # synthetic uniform-token corpus has little learnable signal on CPU
    # scales; assert training is stable (not diverging) rather than a
    # strict descent
    assert result["final_loss"] < result["first_loss"] + 0.05, result
    print(f"loss {result['first_loss']:.3f} → {result['final_loss']:.3f} "
          f"over {result['steps_run']} steps; "
          f"dedup dropped {result['dedup']['dropped']} near-duplicate "
          f"sequences of {result['dedup']['seen']}")


if __name__ == "__main__":
    main()
