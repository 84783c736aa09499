"""MiniCPM3-4B as published: the widths of ``minicpm3_4b`` with the
model's own output head, its norm epsilon and its muP multipliers
(embeddings x 12, each residual branch x 1.4 / sqrt(62), the head's
input x 256 / 2560).  LongRoPE's short factors are not applied: plain
RoPE.  [hf:openbmb/MiniCPM3-4B]

``minicpm3_4b`` keeps the tied, unscaled variant that the CPU fixtures
check against the plain MLA reference."""

import dataclasses

from repro.configs.minicpm3_4b import CONFIG as _TIED

CONFIG = dataclasses.replace(
    _TIED,
    name="minicpm3-4b-published",
    tie_embeddings=False,
    norm_eps=1e-5,
    scale_emb=12.0,
    scale_depth=1.4,
    dim_model_base=256,
)
