"""The benchmark's one traffic generator.

Every traffic mix is a JSON file of parameters under ``bench/traffic/``,
found by its name; this module turns a mix, a configuration and a seed into
the inputs of one run. Nothing here reads the program: the Zipf law and
the paper's Table-1 profiles are copied below so that a later change to the
program cannot move the yardstick.

Two modes:

* ``"open"``: independent users. ``round(rate_per_s * seconds)`` requests
  whose due times are uniform order statistics over the window, i.e. a
  Poisson process conditioned on its count, so every seed offers the same
  amount of work.
* ``"closed"``: offline scoring. A ring of ``ring`` distinct batches of
  ``batch`` samples, scored back to back.

Each request or sample has ``dense`` (``n_dense`` float32 features, standard
normal) and ``sparse`` ids, in one of three layouts by the configuration's
``multi_hot``:

* ``1`` (or absent): one id per field, ``(n, F)``;
* an integer ``L > 1``: a bag of ``L`` positions per field, ``(n, F, L)``,
  the first ``len`` of them ids and the rest -1, with ``len ~
  Poisson(bag.mean)`` clipped to ``[bag.min, bag.max]``;
* a list of one length ``L_f`` per field, as MLPerf's multi-hot DLRM-DCNv2
  states its ``multi_hot_sizes``: field f's bag holds exactly ``L_f`` ids,
  laid out as ``(n, F, max L_f)`` with -1 after them (``(n, F)`` where every
  ``L_f`` is 1). Its lengths are fixed, so a mix that gives it a ``bag`` law
  (or a Table-1 ``profile``, which brings one) is an error.

Ids are drawn per field from a Zipf(``ids.a``) law over the field's
vocabulary (only for the positions that hold ids, in the list layout), and
hot ranks are scattered over the ids by a seeded permutation of each field,
as in the program's ``zipf_popularity``.
"""
from __future__ import annotations

import dataclasses
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC_DIR = os.path.join(HERE, "traffic")

#: The UpDLRM paper's Table 1: average reduction (bag length) and items per
#: dataset, with a Zipf exponent per hotness tier (copied from the program's
#: ``data/synthetic.py`` ``WORKLOADS``).
TABLE1 = {
    "clo": dict(avg_reduction=52.91, n_items=2_685_059, zipf_a=0.60),
    "home": dict(avg_reduction=67.56, n_items=1_301_225, zipf_a=0.65),
    "meta1": dict(avg_reduction=107.2, n_items=5_783_210, zipf_a=0.90),
    "meta2": dict(avg_reduction=188.6, n_items=5_999_981, zipf_a=0.95),
    "read": dict(avg_reduction=245.8, n_items=2_360_650, zipf_a=1.18),
    "read2": dict(avg_reduction=374.08, n_items=2_360_650, zipf_a=1.22),
}

#: Ranks below this are drawn from the exact discrete law; above it from the
#: continuous power law rounded to the nearest rank, which gives rank k the
#: mass of k^-a to within a(a+1)/(24 k^2): under 1e-6 here.
ZIPF_HEAD = 1 << 10


def load_mix(name: str, traffic_dir: str = TRAFFIC_DIR) -> dict:
    """The mix ``<traffic_dir>/<name>.json``, with any Table-1 ``profile``
    filled in."""
    path = os.path.join(traffic_dir, f"{name}.json")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    with open(path) as f:
        mix = json.load(f)
    mix.setdefault("name", name)
    prof = mix.get("profile")
    if prof is not None:
        t1 = TABLE1[prof]
        mix.setdefault("bag", {}).setdefault("mean", t1["avg_reduction"])
        mix.setdefault("ids", {}).setdefault("a", t1["zipf_a"])
    return mix


def _power_cdf_inv(u: np.ndarray, lo: float, hi: float, a: float
                   ) -> np.ndarray:
    """Inverse CDF of the density x^-a on [lo, hi]."""
    if abs(a - 1.0) < 1e-12:
        return lo * np.exp(u * np.log(hi / lo))
    e = 1.0 - a
    return (lo ** e + u * (hi ** e - lo ** e)) ** (1.0 / e)


def zipf_ranks(rng: np.random.Generator, n_items: int, a: float,
               size: int) -> np.ndarray:
    """0-based ranks r < n_items with P(r) proportional to (r + 1)^-a.

    The first ``ZIPF_HEAD`` ranks come from the exact cdf; the tail from the
    power law on [head + 0.5, n + 0.5], rounded: the same law to within
    O(head^-2) of each rank's mass.
    """
    h = min(ZIPF_HEAD, n_items)
    w = np.arange(1, h + 1, dtype=np.float64) ** -a
    cdf = np.cumsum(w)
    head_mass = cdf[-1]
    if h < n_items:
        lo, hi = h + 0.5, n_items + 0.5
        tail_mass = (np.log(hi / lo) if abs(a - 1.0) < 1e-12
                     else (hi ** (1 - a) - lo ** (1 - a)) / (1 - a))
    else:
        tail_mass = 0.0
    u = rng.random(size) * (head_mass + tail_mass)
    out = np.empty(size, np.int64)
    head = u < head_mass
    out[head] = np.searchsorted(cdf, u[head], side="right")
    if tail_mass:
        ut = (u[~head] - head_mass) / tail_mass
        x = _power_cdf_inv(ut, h + 0.5, n_items + 0.5, a)
        out[~head] = np.clip(np.floor(x + 0.5).astype(np.int64) - 1,
                             h, n_items - 1)
    return np.minimum(out, n_items - 1)


def draw_ids(rng: np.random.Generator, vocab: int, a: float, shape: tuple,
             perm: np.ndarray) -> np.ndarray:
    """int32 ids in [0, vocab) of ``shape``: Zipf(``a``) ranks, scattered
    over the ids by ``perm`` (the field's rank -> id map)."""
    ranks = zipf_ranks(rng, vocab, a, int(np.prod(shape)))
    return perm[ranks].astype(np.int32).reshape(shape)


def field_perms(rng: np.random.Generator, cfg: dict) -> list:
    """One rank -> id permutation per field, fixed for the whole run."""
    return [rng.permutation(v).astype(np.int32) for v in cfg["vocab_sizes"]]


def bag_lengths(rng: np.random.Generator, bag: dict, shape: tuple,
                max_len: int) -> np.ndarray:
    lo = int(bag.get("min", 1))
    hi = min(int(bag.get("max", max_len)), max_len)
    return np.clip(rng.poisson(float(bag["mean"]), shape), lo, hi)


def field_lengths(cfg: dict, mix: dict) -> list | None:
    """The bag length of each field where the configuration's ``multi_hot``
    is a list, else None; a list with a ``bag`` law in the mix raises."""
    lens = cfg.get("multi_hot", 1)
    if not isinstance(lens, list):
        return None
    name = cfg.get("name", "<unnamed>")
    if len(lens) != len(cfg["vocab_sizes"]) or min(lens) < 1:
        raise ValueError(f"configuration {name!r}: multi_hot {lens} must give "
                         f"each of its {len(cfg['vocab_sizes'])} fields a "
                         f"length of 1 or more")
    if "bag" in mix:
        raise ValueError(f"configuration {name!r} fixes each field's bag "
                         f"length (multi_hot {lens}), but mix "
                         f"{mix.get('name', '<unnamed>')!r} gives a bag law "
                         f"{mix['bag']}: a mix for it states none")
    return lens


def samples(rng: np.random.Generator, cfg: dict, mix: dict, n: int,
            perms: list) -> dict:
    """``n`` samples of the configuration's inputs under the mix."""
    vocab = cfg["vocab_sizes"]
    a = float(mix["ids"]["a"])
    dense = rng.standard_normal((n, cfg["n_dense"])).astype(np.float32)
    lens = field_lengths(cfg, mix)
    if lens is not None:
        sparse = np.full((n, len(vocab), max(lens)), -1, np.int32)
        for f, (v, length, p) in enumerate(zip(vocab, lens, perms)):
            sparse[:, f, :length] = draw_ids(rng, v, a, (n, length), p)
        return {"dense": dense,
                "sparse": sparse[:, :, 0] if max(lens) == 1 else sparse}
    L = int(cfg.get("multi_hot", 1))
    if L == 1:
        sparse = np.stack([draw_ids(rng, v, a, (n,), p)
                           for v, p in zip(vocab, perms)], axis=1)
        return {"dense": dense, "sparse": sparse}
    sparse = np.stack([draw_ids(rng, v, a, (n, L), p)
                       for v, p in zip(vocab, perms)], axis=1)   # (n, F, L)
    lens = bag_lengths(rng, mix["bag"], (n, len(vocab)), L)
    sparse[np.arange(L)[None, None, :] >= lens[..., None]] = -1
    return {"dense": dense, "sparse": sparse}


@dataclasses.dataclass
class OpenStream:
    """Requests of an open-loop run: ``due_s[i]`` seconds after the window
    opens, request i's features ``feats[k][i]``."""
    due_s: np.ndarray
    feats: dict
    batch: int


@dataclasses.dataclass
class ClosedRing:
    """Batches of a closed-loop run, scored in turn: ``ring[n % len(ring)]``."""
    ring: list
    batch: int


def make_traffic(cfg: dict, mix: dict, seed: int, seconds: float):
    """The run's inputs from the seed: an ``OpenStream`` or a
    ``ClosedRing``. The same (cfg, mix, seed, seconds) gives the same
    inputs."""
    rng = np.random.default_rng([int(seed), 1])
    batch = int(mix["batch"])
    perms = field_perms(rng, cfg)
    if mix["mode"] == "open":
        n = max(1, int(round(float(mix["rate_per_s"]) * seconds)))
        due = np.sort(rng.uniform(0.0, seconds, n))
        return OpenStream(due_s=due, feats=samples(rng, cfg, mix, n, perms),
                          batch=batch)
    if mix["mode"] == "closed":
        ring = [samples(rng, cfg, mix, batch, perms)
                for _ in range(int(mix["ring"]))]
        return ClosedRing(ring=ring, batch=batch)
    raise ValueError(f"unknown traffic mode {mix['mode']!r}")
