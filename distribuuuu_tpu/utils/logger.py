"""Logging (ref: /root/reference/distribuuuu/utils.py:71-82).

The reference uses loguru with a rank-0 file sink ``{OUT_DIR}/{time}.log``
plus an all-rank stderr sink. loguru is not in this environment, so this is
stdlib logging with the same shape: process-0 gets the file sink, every
process logs to stderr tagged with its process index.
"""

from __future__ import annotations

import logging
import os
import sys
import time

import jax

from distribuuuu_tpu.config import cfg

_LOGGER_NAME = "distribuuuu_tpu"
_configured = False


def setup_logger(rank: int | None = None) -> logging.Logger:
    """``rank`` defaults to ``jax.process_index()`` — which initializes
    the backend and so takes every chip the process can see. A process
    that must stay off the chips (the fleet router) passes its rank."""
    global _configured
    logger = logging.getLogger(_LOGGER_NAME)
    if _configured:
        return logger
    logger.setLevel(logging.INFO)
    logger.propagate = False
    if rank is None:
        rank = jax.process_index()
    fmt = logging.Formatter(
        fmt=f"%(asctime)s | %(levelname)s | p{rank} | %(message)s",
        datefmt="%Y-%m-%d %H:%M:%S",
    )
    stream = logging.StreamHandler(sys.stderr)
    stream.setFormatter(fmt)
    logger.addHandler(stream)
    if rank == 0:
        os.makedirs(cfg.OUT_DIR, exist_ok=True)
        fh = logging.FileHandler(os.path.join(cfg.OUT_DIR, f"{time.time()}.log"))
        fh.setFormatter(fmt)
        logger.addHandler(fh)
        logger.info("config:\n%s", cfg.dump())
    _configured = True
    return logger


def get_logger() -> logging.Logger:
    logger = logging.getLogger(_LOGGER_NAME)
    if not logger.handlers:
        # Usable before setup (e.g. in tests): stderr only, no file sink.
        h = logging.StreamHandler(sys.stderr)
        h.setFormatter(logging.Formatter("%(asctime)s | %(levelname)s | %(message)s"))
        logger.addHandler(h)
        logger.setLevel(logging.INFO)
        logger.propagate = False
    return logger
