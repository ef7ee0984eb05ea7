"""Train a ~100M-parameter LM (xlstm-125m, the full assigned config) for a
few hundred steps on the host mesh with the production substrate: sharded
params, checkpointing, fault-tolerant loop.

Run:  PYTHONPATH=src python examples/train_lm.py          (full xlstm-125m)
      PYTHONPATH=src python examples/train_lm.py --reduced --steps 50
"""
import sys

from repro.launch.train import main
from repro.runtime.compile_cache import use_compile_cache

if __name__ == "__main__":
    use_compile_cache()
    argv = sys.argv[1:] or [
        "--arch", "xlstm-125m", "--steps", "300", "--batch", "8",
        "--seq", "128", "--lr", "3e-3", "--log-every", "20",
        "--checkpoint-every", "100",
    ]
    raise SystemExit(main(argv))
