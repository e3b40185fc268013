"""End-to-end driver: asynchronously train a transformer LM with ACE.

Thin wrapper over `repro.launch.train.train` (the scanned real-model path)
— a ~0.8M-param yi-family reduced model by default (CPU-friendly); pass
--hundred-m for a ~100M-param model (slow on CPU, the config the
deliverable names). Loss on the synthetic Markov token stream should fall
from ~ln(vocab) toward ~2-3 within a few hundred steps.

Run:  PYTHONPATH=src python examples/train_lm.py [--hundred-m] [--steps 300]
"""
import argparse
import sys

from repro.launch.train import train

ap = argparse.ArgumentParser()
ap.add_argument("--hundred-m", action="store_true")
ap.add_argument("--steps", type=int, default=300)
ap.add_argument("--algo", default="ace")
args = ap.parse_args()

if args.hundred_m:
    # ~100M params: 8 layers x d_model 1024 (vocab 4096)
    size = dict(d_model=1024, layers=8, vocab=4096, seq=512)
else:
    size = dict(d_model=256, layers=4, vocab=512, seq=256)

final_loss = train(arch="yi-9b", reduced=True, batch=8, steps=args.steps,
                   algo=args.algo, **size).loss
sys.exit(0 if final_loss < 5.5 else 1)
