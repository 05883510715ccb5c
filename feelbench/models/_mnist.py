"""The benchmark's own copy of the MNIST-shaped generator and the shard
partitioner: the yardstick the paper nets' inputs come from.

Copied from the program's ``repro.data.synthetic.generate`` and
``repro.data.partition.partition`` so that a later change to either
cannot change what the benchmark measures.  Same arithmetic, same
random streams: for one seed both produce the same arrays.

- ``generate``: per class a smoothed random prototype plus a rank-4
  style subspace; a sample is ``prototype + style @ coeffs + noise``,
  clipped to [0, 1] and stored as uint8, ordered by class.
- ``partition``: sort by label, cut into shards of ``shard_size``,
  hold out ``test_fraction`` of the shards as the test split, and give
  each device U[min, max] shards, rescaled (floor 1) to fit the pool.
  One departure from the copy: the shard counts are drawn once from the
  configuration's ``counts_seed`` and dealt out in a seeded order, so
  that every seed runs the same work (see ``partition``).
"""

from __future__ import annotations

import numpy as np

NUM_CLASSES = 10
IMAGE_SIZE = 28
STYLE_RANK = 4
STYLE_SCALE = 0.35
NOISE_SCALE = 0.15
SMOOTH_PASSES = 2


def _smooth(img: np.ndarray, passes: int) -> np.ndarray:
    for _ in range(passes):
        padded = np.pad(img, ((1, 1), (1, 1)), mode="edge")
        img = (padded[:-2, 1:-1] + padded[2:, 1:-1] + padded[1:-1, :-2]
               + padded[1:-1, 2:] + padded[1:-1, 1:-1]) / 5.0
    return img


def _prototypes(seed: int) -> tuple[np.ndarray, np.ndarray]:
    rng = np.random.default_rng(seed)
    h = IMAGE_SIZE
    protos, styles = [], []
    for _ in range(NUM_CLASSES):
        p = _smooth(rng.standard_normal((h, h)), SMOOTH_PASSES)
        p = (p - p.min()) / max(p.max() - p.min(), 1e-6)
        protos.append(p)
        styles.append(np.stack([
            _smooth(rng.standard_normal((h, h)), SMOOTH_PASSES)
            for _ in range(STYLE_RANK)]))
    return np.asarray(protos, np.float32), np.asarray(styles, np.float32)


def generate(seed: int, samples_per_class: int
             ) -> tuple[np.ndarray, np.ndarray]:
    """(images uint8 (N, 28, 28), labels int32 (N,)), ordered by class."""
    protos, styles = _prototypes(seed)
    rng = np.random.default_rng(seed + 1)
    images, labels = [], []
    for c in range(NUM_CLASSES):
        coeff = rng.standard_normal(
            (samples_per_class, STYLE_RANK)).astype(np.float32)
        x = (protos[c][None]
             + STYLE_SCALE * np.einsum("nr,rhw->nhw", coeff, styles[c])
             + NOISE_SCALE * rng.standard_normal(
                 (samples_per_class, IMAGE_SIZE, IMAGE_SIZE)
             ).astype(np.float32))
        x = np.clip(x, 0.0, 1.0)
        images.append((x * 255.0).astype(np.uint8))
        labels.append(np.full((samples_per_class,), c, np.int32))
    return np.concatenate(images), np.concatenate(labels)


def _shard_counts(rng: np.random.Generator, num_devices: int,
                  num_shards: int, lo: int, hi: int) -> np.ndarray:
    """U[lo, hi] shards a device, rescaled (floor ``lo``) to the pool."""
    counts = rng.integers(lo, hi + 1, size=num_devices)
    if int(counts.sum()) > num_shards:
        scaled = np.maximum(
            lo, np.floor(counts * num_shards / int(counts.sum()))
            .astype(np.int64))
        while scaled.sum() > num_shards:
            scaled[int(np.argmax(scaled))] -= 1
        counts = scaled
    return counts.astype(np.int64)


def partition(images: np.ndarray, labels: np.ndarray, seed: int, *,
              num_devices: int, num_shards: int, shard_size: int,
              min_shards: int, max_shards: int,
              test_fraction: float, counts_seed: int) -> dict:
    """The paper's shard protocol; dense ``(K, cap, ...)`` arrays.

    The shard counts are one draw from ``counts_seed``, fixed by the
    configuration, dealt to the devices in an order drawn from ``seed``:
    every seed then gets the same set of device sizes, so the same
    ``cap`` (the program's shapes) and the same work, and only which
    device holds which size and which shards changes.

    Returns numpy arrays: ``images`` (K, cap, ...) uint8, ``labels``
    (K, cap) int32, ``mask`` (K, cap) float32, ``sizes`` (K,) int32,
    ``test_images`` (T, ...) uint8 and ``test_labels`` (T,) int32, where
    ``...`` is the shape of one sample of ``images``.
    """
    n = num_shards * shard_size
    if images.shape[0] < n:
        raise ValueError(f"need {n} samples, got {images.shape[0]}")
    order = np.argsort(labels[:n], kind="stable")
    images, labels = images[:n][order], labels[:n][order]
    rng = np.random.default_rng(seed)
    n_test = max(1, int(round(num_shards * test_fraction)))
    shard_ids = rng.permutation(num_shards)
    test_shards, train_shards = shard_ids[:n_test], shard_ids[n_test:]

    def rows(s: int) -> slice:
        return slice(s * shard_size, (s + 1) * shard_size)

    test_images = np.concatenate([images[rows(s)] for s in test_shards])
    test_labels = np.concatenate([labels[rows(s)] for s in test_shards])
    counts = _shard_counts(np.random.default_rng(counts_seed), num_devices,
                           len(train_shards), min_shards, max_shards)
    counts = counts[rng.permutation(num_devices)]
    cap = int(counts.max()) * shard_size
    out_images = np.zeros((num_devices, cap) + images.shape[1:], np.uint8)
    out_labels = np.zeros((num_devices, cap), np.int32)
    out_mask = np.zeros((num_devices, cap), np.float32)
    cursor = 0
    for k in range(num_devices):
        for j in range(int(counts[k])):
            sl = rows(train_shards[cursor])
            cursor += 1
            dst = slice(j * shard_size, (j + 1) * shard_size)
            out_images[k, dst] = images[sl]
            out_labels[k, dst] = labels[sl]
            out_mask[k, dst] = 1.0
    return {"images": out_images, "labels": out_labels, "mask": out_mask,
            "sizes": out_mask.sum(axis=1).astype(np.int32),
            "test_images": test_images, "test_labels": test_labels}


def make(seed: int, cfg: dict) -> dict:
    """The configuration's whole data set from one seed."""
    d = cfg["data"]
    images, labels = generate(seed, d["samples_per_class"])
    return partition(images, labels, seed + 1,
                     num_devices=cfg["devices"],
                     num_shards=d["num_shards"],
                     shard_size=d["shard_size"],
                     min_shards=d["min_shards"],
                     max_shards=d["max_shards"],
                     test_fraction=d["test_fraction"],
                     counts_seed=d["counts_seed"])
