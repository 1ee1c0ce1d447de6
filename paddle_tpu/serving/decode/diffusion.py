"""The host's side of block-diffusion generation (a WINDOW model:
models/block_diffusion_lm.py, docs/SERVING.md "Window models"): what a block
is on the host, and the schedule that unmasks it.

A slot's block is B token ids, `MASK` where a position is still masked, and
B flags saying which. A denoising forward (engine.py::window_step) hands the
host, per position, the most likely token and the model's confidence in it;
the schedule here, the family's ``low_confidence_static``, fixes the
``quota`` most confident of the masked positions to their picks, ties to the
lower position, and a fixed token never changes. ``quota`` is static per
block: ⌈masked₀ / denoising_steps⌉, masked₀ the positions masked when the
block was opened, so a block takes at most ``denoising_steps`` denoising
forwards, then one commit forward. What crosses from the device is O(S·B):
ids and confidences, never a logits row.
"""
from __future__ import annotations

import numpy as np

from ..errors import InvalidRequest

__all__ = ['denoise_quota', 'unmask_most_confident',
           'validate_denoising_steps']


def validate_denoising_steps(value, window):
    """``value`` as the denoising steps of a block of ``window`` positions:
    an int in 1..window (1: the whole block in one forward; window: one
    position a forward). Raises InvalidRequest naming the field."""
    if isinstance(value, bool) or not isinstance(value, (int, np.integer)):
        raise InvalidRequest(
            f'denoising_steps must be an integer, got {value!r}')
    if not 1 <= int(value) <= int(window):
        raise InvalidRequest(
            f'denoising_steps={value} is outside 1..{window} (the model\'s '
            f'block length)')
    return int(value)


def denoise_quota(masked0, denoising_steps):
    """Positions every denoising forward of a block unmasks: ⌈masked₀ /
    denoising_steps⌉ (the last forward takes what is left)."""
    return -(-int(masked0) // int(denoising_steps))


def unmask_most_confident(blocks, masked, ids, conf, quota):
    """One denoising forward's unmasking, for all S slots at once, IN PLACE:
    of slot s's masked positions the ``quota[s]`` most confident (``conf``
    (S, B) float32, ties to the lower position) take their picks ``ids``
    (S, B) in ``blocks`` (S, B) and leave ``masked`` (S, B). A slot with
    quota 0 or nothing masked (a committing or an idle one) is untouched.
    Returns the number of positions unmasked."""
    score = np.where(masked, conf, -np.inf)
    # stable: of equal confidences the lower position ranks first
    order = np.argsort(-score, axis=1, kind='stable')
    rank = np.empty_like(order)
    np.put_along_axis(rank, order, np.arange(order.shape[1])[None, :], 1)
    take = masked & (rank < np.asarray(quota)[:, None])
    blocks[take] = ids[take]
    masked[take] = False
    return int(take.sum())
