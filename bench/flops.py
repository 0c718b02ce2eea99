"""Model FLOPs of one served sampler step, counted from shapes by the
config's form (``forms/<form>.py``: ``forward_flops`` and ``part_flops``).

Matmuls only (2 FLOPs a multiply-add), which is what the chip's matrix
units do; norms, softmax and the other elementwise work are left out, as
model FLOPs are.  Only real request rows count: a padded row is no model
work.
"""
from __future__ import annotations

from bench import spec


def branches(config: dict) -> int:
    """Forwards a step: two under classifier-free guidance."""
    return 2 if config["sampler"].get("guidance_scale", 1.0) != 1.0 else 1


def step_flops(config: dict, rows: int, latent: int) -> float:
    """One sampler step of ``rows`` requests of ``latent`` tokens."""
    return branches(config) * spec.form(config["form"]).forward_flops(
        config, rows, latent)


def part_flops(config: dict, rows: int, latent: int) -> dict[str, float]:
    """One sampler step's FLOPs by the parts of the form's block."""
    return {k: branches(config) * v for k, v in spec.form(
        config["form"]).part_flops(config, rows, latent).items()}
