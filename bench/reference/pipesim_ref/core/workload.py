"""hour_of_week_weights from the repro package's core/workload.py."""
from __future__ import annotations

import numpy as np


def hour_of_week_weights() -> np.ndarray:
    """[168] relative arrival rates, Monday 00:00 first. Weekday double peak
    (10:00, 15:00-16:00), lunch dip, low nights; weekends damped."""
    hours = np.arange(24)
    day = (
        0.25
        + 0.9 * np.exp(-0.5 * ((hours - 10.0) / 2.0) ** 2)
        + 1.0 * np.exp(-0.5 * ((hours - 15.5) / 2.2) ** 2)
        - 0.18 * np.exp(-0.5 * ((hours - 12.5) / 0.9) ** 2)
    )
    week = []
    for dow in range(7):
        scale = 1.0 if dow < 5 else 0.38
        jitter = 1.0 + 0.05 * np.cos(dow)  # mild day-to-day variation
        week.append(day * scale * jitter)
    w = np.concatenate(week)
    return w / w.mean()
