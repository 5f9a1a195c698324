"""End-to-end training driver: a ~100M-param llama-style model for a few
hundred steps on synthetic data, with GPULZ-compressed checkpoints and
straggler-guarded steps.

    PYTHONPATH=src python examples/train_tiny_lm.py [--steps 200]

(~100M params: 8 layers x d_model 768 x ffn 2048, vocab 32k.  On this CPU
container a step takes a few seconds; pass --tiny for a quick smoke run.)
"""

import argparse
import sys

sys.argv = [sys.argv[0]]  # re-parsed below via launch.train's CLI

from repro.launch import train as train_cli


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_tiny_lm_ckpt")
    args, _ = ap.parse_known_args()

    class A:
        arch = "llama3.2-1b"
        reduced = bool(args.tiny)
        d_model = 0 if args.tiny else 768
        d_ff = 0 if args.tiny else 2048
        layers = 0 if args.tiny else 8
        steps = 30 if args.tiny else args.steps
        batch = 4
        seq = 256
        lr = 3e-4
        microbatches = 1
        ckpt_dir = args.ckpt_dir
        ckpt_every = 50
        heartbeat = "/tmp/repro_tiny_lm_heartbeat.json"
        log_every = 10

    losses, _ = train_cli.train_loop(A)
    assert losses[-1] < losses[0], "loss must decrease"
    print("OK: loss decreased; checkpoints at", A.ckpt_dir)


if __name__ == "__main__":
    main()
