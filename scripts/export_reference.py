"""Export a sat_tpu checkpoint into the reference's flat TF1 npy layout.

Migration in the reverse direction of ``--load`` + reference import: the
output file is a ``{var.name: value}`` dict exactly as the reference's own
``save()`` writes (/root/reference/base_model.py:242-249), so the
reference's ``load()`` (per-name assign with missing-key tolerance,
base_model.py:270-277) ingests a sat_tpu-trained model directly.
Optimizer slots are not exported.

Usage: python scripts/export_reference.py <checkpoint.npz> <out.npy>
       [--config config.json]

The config sidecar (written next to every checkpoint) supplies the model
architecture; pass --config explicitly if the checkpoint was moved away
from its sidecar.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("checkpoint", help="sat_tpu .npz checkpoint")
    ap.add_argument("out", help="output .npy in reference layout")
    ap.add_argument(
        "--config", default=None,
        help="config.json (default: sidecar next to the checkpoint)",
    )
    args = ap.parse_args()

    os.environ["JAX_PLATFORMS"] = "cpu"  # host-side tensor shuffling
    import jax

    from sat_tpu.config import Config
    from sat_tpu.train.checkpoint import (
        export_reference_checkpoint,
        restore_checkpoint,
    )
    from sat_tpu.train.step import create_train_state

    config_path = args.config or os.path.join(
        os.path.dirname(os.path.abspath(args.checkpoint)), "config.json"
    )
    config = Config.load(config_path)
    state = create_train_state(jax.random.PRNGKey(0), config)
    state, count = restore_checkpoint(state, args.checkpoint)
    print(f"{count} tensors restored from {args.checkpoint}")
    n = export_reference_checkpoint(state, args.out)
    print(f"{n} tensors exported in reference layout -> {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
