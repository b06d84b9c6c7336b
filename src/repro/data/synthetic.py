"""Synthetic stand-ins for the paper's two datasets (offline container).

The paper uses the first 1000 samples of:
  * Chicago-taxi-trips **fares** [3]  — non-negative dollar amounts quantized
    to $0.25 steps, heavy-tailed, many repeated values (few distinct bins).
  * UCI **gas-turbine CO/NOx emissions** [5] — smooth continuous sensor
    readings in a narrow physical range.

The generators below match those published characteristics (support,
quantization, tail shape, autocorrelation).  DESIGN.md §7 records this
substitution; every benchmark reports which generator was used.
"""
from __future__ import annotations

import numpy as np


def chicago_taxi_fares(n: int = 1000, seed: int = 0) -> np.ndarray:
    """Fare-like: 3.25 base + distance/time components, $0.25 quantization,
    log-normal tail, occasional flat airport fares."""
    rng = np.random.default_rng(seed)
    miles = rng.lognormal(mean=0.8, sigma=0.9, size=n)
    fare = 3.25 + 2.25 * miles + 0.50 * rng.poisson(3, n)
    # mostly $0.25-quantized; ~25% carry odd cents (tips/tolls folded in)
    fare = np.round(fare / 0.25) * 0.25
    cents = rng.random(n) < 0.25
    fare[cents] += np.round(rng.random(cents.sum()), 2)
    flat = rng.random(n) < 0.06
    fare[flat] = rng.choice([35.0, 41.75, 52.0], flat.sum())
    return np.clip(np.round(fare, 2), 3.25, 250.0).astype(np.float64)


def gas_turbine_emissions(n: int = 1000, seed: int = 1) -> np.ndarray:
    """CO-emission-like: slow AR(1) drift around ~2.4 mg/m^3 with small
    measurement noise; strictly positive, narrow range (a few binades)."""
    rng = np.random.default_rng(seed)
    # the stream of one normal(0, 0.03) drift and one normal(0, 0.004) noise
    # draw per sample, alternating, drawn in bulk (same values, bitwise)
    z = rng.standard_normal(2 * n)
    noise = 0.004 * z[1::2]
    level = 2.4
    x = np.empty(n)
    for i, d in enumerate((0.03 * z[0::2]).tolist()):
        level += 0.02 * (2.4 - level) + d
        x[i] = level
    x += noise
    # the real UCI CSV carries ~4-5 significant decimal digits (parsed text)
    return np.round(np.clip(x, 0.2, 20.0), 4).astype(np.float64)


DATASETS = {
    "taxi_fares": chicago_taxi_fares,
    "gas_turbine": gas_turbine_emissions,
}
