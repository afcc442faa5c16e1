"""Seeded bank of synthetic keyword audio that the streams read from.

A copy of the repository's Google-Speech-Commands stand-in
(``repro.data.gscd``): 12 classes of 1 s, 16 kHz clips, quantized to
8-bit offset-binary codes.  Keyword classes are harmonic chirps with
class-specific formants, syllable envelopes and onsets, plus noise;
class 10 is babble and class 11 is silence.  The bank is made once in
set-up and streams read it at offsets, so nothing is synthesized inside
the measured window.
"""
from __future__ import annotations

import numpy as np

SR = 16000
N_CLASSES = 12
_RECIPES = [
    (220, 880, 0.0), (330, 1320, 0.2), (440, 660, -0.2), (550, 1100, 0.1),
    (660, 990, -0.1), (290, 1450, 0.3), (370, 740, -0.3), (490, 1470, 0.15),
    (610, 915, -0.15), (260, 1560, 0.25),
]


def _keyword(rng: np.random.Generator, cls: int, n: int) -> np.ndarray:
    f0, f1, chirp = _RECIPES[cls]
    t = np.arange(n) / SR
    jitter = rng.uniform(0.9, 1.1)
    n_syll = 1 + cls % 3
    syl_rate = 2.5 + 0.9 * (cls % 4)
    onset = 0.05 + 0.02 * (cls % 5) + rng.uniform(0, 0.04)
    dur = (0.30 + 0.05 * (cls % 4)) * rng.uniform(0.9, 1.1)
    env = np.zeros_like(t)
    for s_i in range(n_syll):
        c = onset + dur * (s_i + 0.5) / n_syll
        env += np.exp(-0.5 * ((t - c) / (dur / (2.5 * n_syll))) ** 2)
    env *= 0.75 + 0.25 * np.sin(2 * np.pi * syl_rate * t)
    phase0 = rng.uniform(0, 2 * np.pi)
    f_t0 = f0 * jitter * (1 + chirp * t)
    f_t1 = f1 * jitter * (1 - 0.5 * chirp * t)
    sig = env * (
        np.sin(2 * np.pi * np.cumsum(f_t0) / SR + phase0)
        + 0.6 * np.sin(2 * np.pi * np.cumsum(f_t1) / SR)
        + 0.3 * np.sin(2 * np.pi * np.cumsum(2.1 * f_t0) / SR)
    )
    return sig + rng.standard_normal(n) * 0.05


def clip(rng: np.random.Generator, cls: int, n: int = SR) -> np.ndarray:
    """One clip of class ``cls`` as u8 offset-binary codes."""
    if cls < 10:
        sig = _keyword(rng, cls, n)
    elif cls == 10:
        a, b = rng.integers(0, 10, 2)
        sig = 0.5 * _keyword(rng, a, n) + 0.5 * _keyword(rng, b, n)[::-1]
    else:
        sig = np.clip(rng.standard_normal(n) * rng.uniform(0.01, 0.06), -1, 1)
    if cls != 11:
        sig = sig / (np.max(np.abs(sig)) + 1e-6) * rng.uniform(0.5, 0.95)
    return np.clip(np.round(sig * 127) + 128, 0, 255).astype(np.uint8)


def bank(seed: int, seconds: int) -> np.ndarray:
    """``seconds`` one-second clips of random classes, end to end."""
    rng = np.random.default_rng([seed, 0xA0D1])
    return np.concatenate([clip(rng, int(rng.integers(N_CLASSES)))
                           for _ in range(seconds)])
