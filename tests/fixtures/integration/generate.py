"""Generate golden baselines for the integration regression suite
(ref: `IntegrationTestBaselineGenerator.java` — run once, commit the
outputs; the runner compares every subsequent round against them).

Run from the repo root under the hermetic CPU env the test suite uses:

  JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python tests/fixtures/integration/generate.py
"""
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "..", ".."))          # tests/
sys.path.insert(0, os.path.join(HERE, "..", "..", ".."))    # repo root

from integration_cases import CASES, run_case  # noqa: E402


def main():
    for name in CASES:
        params, preds, losses = run_case(name)
        path = os.path.join(HERE, f"{name}.npz")
        np.savez_compressed(
            path, __preds__=preds, __losses__=losses,
            **{f"p:{k}": v for k, v in params.items()})
        print(f"{name}: {len(params)} param tensors, preds "
              f"{preds.shape}, final loss {losses[-1]:.6f}")


if __name__ == "__main__":
    main()
