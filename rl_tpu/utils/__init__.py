import logging

from .seeding import fold_seed, key_chain, seed_generator
from .timing import timeit

logger = logging.getLogger("rl_tpu")
if not logger.handlers:
    _h = logging.StreamHandler()
    _h.setFormatter(logging.Formatter("%(asctime)s [%(name)s][%(levelname)s] %(message)s"))
    logger.addHandler(_h)
    logger.setLevel(logging.INFO)
    logger.propagate = False  # avoid double emission via the root logger

__all__ = [
    "logger",
    "timeit",
    "seed_generator",
    "key_chain",
    "fold_seed",
]
