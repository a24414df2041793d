"""Plain float32 references: they import nothing of the program under test."""
from __future__ import annotations

import numpy as np


def gap_of(ref_logits, tokens) -> np.ndarray:
    """How far each chosen token's reference logit lies below the
    reference's best at that position (0 where they agree); the same for
    every architecture."""
    ref = np.asarray(ref_logits, np.float64)
    tokens = np.asarray(tokens)
    return ref.max(axis=-1) - ref[np.arange(len(tokens)), tokens]
