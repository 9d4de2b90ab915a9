"""The ``records`` traffic's data: a RecordIO file of JPEG images.

Written once per checkout into the benchmark's cache directory
(``benchmarks/.cache/``, listed in the root ``.gitignore``), keyed by
every parameter that shapes it, so only the first run in a checkout pays
for it. The *content* comes from the traffic file's own
``content_seed``, not from ``--seed``: every seed then decodes the same
set of images — the same work — in another order, with other crops and
mirrors (``seed_data = --seed`` on the iterator), and set-up stays the
same from run to run.

Content is photograph-like: a smooth low-frequency colour field, a
mid-frequency layer and fine texture on top. Flat blocks (what
``bench.py:_write_synthetic_recordio`` packs) encode to a few KB and
decode faster than photographs do; pure noise does the opposite.
"""

from __future__ import annotations

import hashlib
import json
import os
from concurrent import futures

import numpy as np


def _encoder(quality: int):
    """``uint8 HWC RGB -> JPEG bytes`` with what is installed."""
    try:
        import cv2
    except ImportError:
        import io
        from PIL import Image

        def encode(img):
            b = io.BytesIO()
            Image.fromarray(img).save(b, "JPEG", quality=quality)
            return b.getvalue()
        return encode, None

    def encode(img):
        ok, buf = cv2.imencode(".jpg", img[:, :, ::-1],
                               [cv2.IMWRITE_JPEG_QUALITY, quality])
        if not ok:
            raise RuntimeError("cv2.imencode failed")
        return buf.tobytes()
    return encode, cv2


def _upsample(small, size, cv2):
    """A ``(h, w, 3)`` float field blown up smoothly to ``size``."""
    if cv2 is not None:
        return cv2.resize(small, (size[1], size[0]),
                          interpolation=cv2.INTER_CUBIC)
    from PIL import Image
    chans = [np.asarray(Image.fromarray(small[:, :, c]).resize(
        (size[1], size[0]), Image.BICUBIC)) for c in range(3)]
    return np.stack(chans, axis=-1)


def image(seed, size, texture, cv2):
    """Photograph-like ``uint8`` image ``seed`` of ``size = (h, w)``
    (a generator of its own each, so a pool of threads makes them in
    any order)."""
    rng = np.random.RandomState(seed)
    h, w = size
    base = _upsample(rng.uniform(30, 225, (5, 5, 3)).astype(np.float32),
                     size, cv2)
    mid = _upsample(rng.normal(0, 28, (40, 40, 3)).astype(np.float32),
                    size, cv2)
    # fine texture: a window of one shared noise tile at a random
    # offset and sign, mostly luminance, so no two images repeat it
    oy = rng.randint(0, texture.shape[0] - h)
    ox = rng.randint(0, texture.shape[1] - w)
    tex = texture[oy:oy + h, ox:ox + w] * rng.choice((-1.0, 1.0))
    img = base + mid + tex[:, :, None] * rng.uniform(0.6, 1.4)
    return np.clip(img, 0, 255).astype(np.uint8)


def key_of(params: dict) -> str:
    blob = json.dumps(params, sort_keys=True).encode("utf-8")
    return hashlib.sha256(blob).hexdigest()[:16]


def ensure(cache_dir: str, *, images: int, stored: tuple, quality: int,
           classes: int, content_seed: int, crop: tuple,
           distinct: int = 0) -> dict:
    """Make sure the record file and its mean image exist; returns
    ``{"rec", "mean", "bytes_per_image", "wrote"}``. ``crop`` is the
    net's ``(y, x)`` input: the mean image is the stored images'
    per-pixel mean, centre-cropped to it (what the iterator's own pass
    would compute from centre crops). ``distinct`` (a divisor of
    ``images``; 0 for all): how many images are made — 5 ms each on one
    thread — and then written again and again, each record with a label
    of its own, until the file holds ``images`` records. A decoder does
    the same work on a copy, and the file is as long."""
    from cxxnet_tpu.io.recordio import ImageRecord, RecordWriter
    distinct = distinct or images
    if images % distinct:
        raise ValueError(f"distinct = {distinct} does not divide "
                         f"images = {images}")
    params = dict(images=images, distinct=distinct, stored=list(stored),
                  quality=quality, classes=classes,
                  content_seed=content_seed, crop=list(crop), version=3)
    root = os.path.join(cache_dir, "records-" + key_of(params))
    meta_path = os.path.join(root, "meta.json")
    rec = os.path.join(root, "train.rec")
    mean = os.path.join(root, "mean.npy")
    if os.path.exists(meta_path):
        with open(meta_path) as f:
            meta = json.load(f)
        return dict(rec=rec, mean=mean, wrote=False,
                    bytes_per_image=meta["bytes_per_image"])
    os.makedirs(root, exist_ok=True)
    encode, cv2 = _encoder(quality)
    rng = np.random.RandomState(content_seed)
    h, w = stored
    texture = rng.normal(0, 14, (2 * h, 2 * w)).astype(np.float32)
    labels = rng.randint(0, classes, images)
    seeds = rng.randint(0, 2 ** 31 - 1, distinct)
    acc = np.zeros((h, w, 3), np.float64)
    encoded = []

    def make(i):
        img = image(seeds[i], (h, w), texture, cv2)
        return img, encode(img)
    # numpy and the encoder let go of the interpreter lock now and then
    with futures.ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        for lo in range(0, distinct, 1024):     # bounds what is in flight
            for img, data in pool.map(
                    make, range(lo, min(lo + 1024, distinct))):
                acc += img
                encoded.append(data)
    with RecordWriter(rec) as out:
        for i in range(images):
            out.write(ImageRecord(
                inst_id=i, data=encoded[i % distinct],
                labels=np.asarray([labels[i]], np.float32)).pack())
    total = sum(map(len, encoded)) * (images // distinct)
    y0, x0 = (h - crop[0]) // 2, (w - crop[1]) // 2
    np.save(mean, (acc / distinct)[y0:y0 + crop[0], x0:x0 + crop[1]]
            .astype(np.float32))
    meta = dict(params, bytes_per_image=total / images)
    tmp = meta_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(meta, f)
    os.replace(tmp, meta_path)      # last: marks the directory whole
    return dict(rec=rec, mean=mean, wrote=True,
                bytes_per_image=meta["bytes_per_image"])
