"""Seeded request payloads: JPEG files the size and shape of ImageNet's.

Pure noise does not compress and a flat image compresses to nothing, so each
image is low-frequency colour structure (coarse noise, upsampled) plus a
little fine grain: with the defaults in a traffic file a 500x375 file lands
between 80 and 150 KB, where ImageNet's own files lie. The same seed gives the
same bytes (the server child remakes sampled images from it for the
reference, with no file passed between the processes).
"""

from __future__ import annotations

import io

import numpy as np


def jpeg_payloads(seed: int, *, count: int, width: int, height: int,
                  coarse_px: int, grain: tuple, quality: int) -> list[bytes]:
    from PIL import Image

    rng = np.random.default_rng([seed, 2])
    out = []
    for _ in range(count):
        coarse = rng.integers(
            0, 256, (max(2, height // coarse_px), max(2, width // coarse_px), 3),
            dtype=np.uint8,
        )
        base = np.asarray(
            Image.fromarray(coarse).resize((width, height), Image.BICUBIC),
            np.float32,
        )
        sigma = rng.uniform(grain[0], grain[1])  # spreads the file sizes
        noisy = base + rng.normal(0.0, sigma, base.shape).astype(np.float32)
        buf = io.BytesIO()
        Image.fromarray(np.clip(noisy, 0, 255).astype(np.uint8)).save(
            buf, format="JPEG", quality=quality
        )
        out.append(buf.getvalue())
    return out


def decode_for_serving(payload: bytes, resize: int, crop: int) -> np.ndarray:
    """The validation transform as published for ImageNet evaluation: decode,
    shorter side to ``resize`` (bilinear), centre crop ``crop`` — raw uint8
    HWC. The benchmark's own copy, for the reference's input."""
    from PIL import Image

    img = Image.open(io.BytesIO(payload)).convert("RGB")
    w, h = img.size
    if w <= h:
        new_w, new_h = resize, int(round(resize * h / w))
    else:
        new_w, new_h = int(round(resize * w / h)), resize
    img = img.resize((new_w, new_h), Image.BILINEAR)
    left, top = (new_w - crop) // 2, (new_h - crop) // 2
    return np.asarray(img.crop((left, top, left + crop, top + crop)), np.uint8)
