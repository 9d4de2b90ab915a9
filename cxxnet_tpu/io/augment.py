"""Image augmentation pipeline (host-side, per-instance).

Reference analogs:
  * ImageAugmenter (/root/reference/src/io/image_augmenter-inl.hpp:13-224):
    OpenCV affine pipeline — rotation (max_rotate_angle / rotate_list /
    fixed ``rotate``), shear, aspect-ratio jitter, random scale
    (min/max_random_scale), random/center crop to (y,x), mirror, fill_value.
  * AugmentIterator (/root/reference/src/io/iter_augment_proc-inl.hpp:22-254):
    crop offsets (rand vs center vs fixed crop_y_start/crop_x_start), mirror,
    ``divideby`` scaling, mean-image subtraction with on-the-fly computation
    and caching, mean_value RGB, max_random_contrast / max_random_illumination.

Arrays are float32 HWC (RGB). cv2 is used when an affine transform is
actually requested; the plain crop/mirror path is pure numpy so the common
case has no cv2 dependency.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple, Union

import numpy as np

# either numpy RNG API (see _ri): the per-item decode rng is a
# Generator(PCG64); long-lived callers still pass RandomState
RngLike = Union[np.random.Generator, np.random.RandomState]


class AugmentParams:
    """Parsed augmentation settings; names match the reference config keys."""

    def __init__(self) -> None:
        self.rand_crop = 0
        self.rand_mirror = 0
        self.mirror = 0
        self.crop_y_start = -1
        self.crop_x_start = -1
        self.max_rotate_angle = 0.0
        self.max_aspect_ratio = 0.0
        self.max_shear_ratio = 0.0
        self.min_crop_size = -1
        self.max_crop_size = -1
        self.min_random_scale = 1.0
        self.max_random_scale = 1.0
        self.min_img_size = 0.0
        self.max_img_size = 1e10
        self.rotate = -1
        self.rotate_list: Sequence[int] = ()
        self.fill_value = 255
        self.max_random_contrast = 0.0
        self.max_random_illumination = 0.0
        self.mean_value: Optional[np.ndarray] = None    # (3,) RGB
        self.mean_img: str = ""
        self.divideby = 1.0
        # -1 = auto (imgrec resolves to 1 when the augmentation chain is
        # uint8-exact — crop/mirror only — and records hold encoded images;
        # see ImageRecordIterator.init). 0/1 are explicit off/on.
        self.device_normalize = -1
        self.scale = 1.0

    def set_param(self, name: str, val: str) -> bool:
        if name == "rand_crop":
            self.rand_crop = int(val)
        elif name == "rand_mirror":
            self.rand_mirror = int(val)
        elif name == "mirror":
            self.mirror = int(val)
        elif name == "crop_y_start":
            self.crop_y_start = int(val)
        elif name == "crop_x_start":
            self.crop_x_start = int(val)
        elif name == "max_rotate_angle":
            self.max_rotate_angle = float(val)
        elif name == "max_aspect_ratio":
            self.max_aspect_ratio = float(val)
        elif name == "max_shear_ratio":
            self.max_shear_ratio = float(val)
        elif name == "min_crop_size":
            self.min_crop_size = int(val)
        elif name == "max_crop_size":
            self.max_crop_size = int(val)
        elif name == "min_random_scale":
            self.min_random_scale = float(val)
        elif name == "max_random_scale":
            self.max_random_scale = float(val)
        elif name == "min_img_size":
            self.min_img_size = float(val)
        elif name == "max_img_size":
            self.max_img_size = float(val)
        elif name == "rotate":
            self.rotate = int(val)
        elif name == "rotate_list":
            self.rotate_list = [int(x) for x in val.split(",") if x]
        elif name == "fill_value":
            self.fill_value = int(val)
        elif name == "max_random_contrast":
            self.max_random_contrast = float(val)
        elif name == "max_random_illumination":
            self.max_random_illumination = float(val)
        elif name == "image_mean":
            self.mean_img = val
        elif name == "mean_value":
            self.mean_value = np.asarray(
                [float(x) for x in val.split(",")], np.float32)
        elif name == "divideby":
            self.divideby = float(val)
        elif name == "device_normalize":
            self.device_normalize = int(val)
        elif name == "scale":
            self.scale = float(val)
        else:
            return False
        return True

    @property
    def needs_affine(self) -> bool:
        return (self.max_rotate_angle > 0 or self.max_shear_ratio > 0
                or self.rotate > 0 or len(self.rotate_list) > 0
                or self.max_aspect_ratio > 0
                or self.min_crop_size > 0
                or self.min_random_scale != 1.0
                or self.max_random_scale != 1.0
                or self.min_img_size > 0
                or self.max_img_size < 1e10)


def mean_cache_path(p: AugmentParams) -> str:
    """Path of the cached mean image (.npy suffix appended when absent;
    ``.binaryproto`` paths pass through — Caffe mean import)."""
    path = p.mean_img
    if path and not path.endswith((".npy", ".binaryproto")):
        path = path + ".npy"
    return path


# -- minimal protobuf wire-format reader (the binaryproto mean import's;
# only the standalone tools/import_caffe.py keeps a copy of its own, being
# a no-package-import CLI)


def read_varint(buf: bytes, pos: int):
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, pos
        shift += 7


def iter_fields(buf: bytes):
    """Yield (field_number, wire_type, value) for one message's bytes."""
    pos = 0
    n = len(buf)
    while pos < n:
        key, pos = read_varint(buf, pos)
        field, wt = key >> 3, key & 7
        if wt == 0:
            val, pos = read_varint(buf, pos)
        elif wt == 1:
            val, pos = buf[pos:pos + 8], pos + 8
        elif wt == 2:
            ln, pos = read_varint(buf, pos)
            val, pos = buf[pos:pos + ln], pos + ln
        elif wt == 5:
            val, pos = buf[pos:pos + 4], pos + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wt}")
        yield field, wt, val


def load_binaryproto_mean(data: bytes, rgb_flip: bool = True) -> np.ndarray:
    """Parse a Caffe ``mean.binaryproto`` (a serialized BlobProto) into
    an (H, W, C) float32 RGB mean image — the classic ImageNet
    preprocessing artifact (reference tools/caffe_converter). Wire-level
    protobuf parsing via the minimal reader above
    (:func:`iter_fields`) — no Caffe/protobuf dependency.
    Caffe blobs are NCHW with BGR channel order; ``rgb_flip`` (default)
    reverses the channel axis so the result matches this framework's
    RGB pipeline.

    BlobProto fields used: legacy dims num=1 channels=2 height=3
    width=4, payload ``data`` (repeated float, field 5, packed or not),
    new-style ``shape`` (field 7: BlobShape{repeated int64 dim=1})."""
    legacy = {1: 0, 2: 0, 3: 0, 4: 0}
    shape: list = []
    chunks: list = []
    for field, wt, val in iter_fields(data):
        if wt == 0 and field in legacy:
            legacy[field] = val
        elif field == 5 and wt == 5:            # unpacked float
            chunks.append(np.frombuffer(val, "<f4"))
        elif field == 5 and wt == 2:            # packed floats
            chunks.append(np.frombuffer(val, "<f4"))
        elif field == 7 and wt == 2:            # BlobShape
            for f2, wt2, v2 in iter_fields(val):
                if f2 != 1:
                    continue
                if wt2 == 0:
                    shape.append(v2)
                elif wt2 == 2:                  # packed dims
                    p = 0
                    while p < len(v2):
                        d, p = read_varint(v2, p)
                        shape.append(d)
    arr = (np.concatenate(chunks) if chunks
           else np.zeros((0,), np.float32))
    if not shape:
        shape = [d for d in (legacy[1], legacy[2], legacy[3], legacy[4])
                 if d]
    if not shape or int(np.prod(shape)) != arr.size:
        raise ValueError(
            f"binaryproto: shape {shape} does not match {arr.size} floats")
    arr = arr.reshape(shape)
    while arr.ndim > 3 and arr.shape[0] == 1:   # (1,C,H,W) -> (C,H,W)
        arr = arr[0]
    if arr.ndim != 3:
        raise ValueError(f"binaryproto: expected a CHW mean, got "
                         f"{arr.shape}")
    arr = np.transpose(arr, (1, 2, 0))          # CHW -> HWC
    if rgb_flip and arr.shape[-1] == 3:
        arr = arr[:, :, ::-1]                   # BGR -> RGB
    return np.ascontiguousarray(arr, np.float32)


def _center_crop_mean(mean: np.ndarray,
                      shape_hwc: Tuple[int, int, int]) -> np.ndarray:
    """Caffe means are usually computed at the resize size (e.g.
    256x256) while this pipeline subtracts post-crop (e.g. 224x224):
    center-crop the imported mean to the input shape — the standard
    Caffe deploy-time treatment of the mean blob."""
    h, w, c = shape_hwc
    mh, mw = mean.shape[:2]
    if (mh, mw) == (h, w):
        return mean
    if mh < h or mw < w or mean.shape[2] != c:
        raise ValueError(
            f"mean image {mean.shape} incompatible with input "
            f"({h}, {w}, {c}); it must be at least the crop size")
    y0, x0 = (mh - h) // 2, (mw - w) // 2
    return np.ascontiguousarray(mean[y0:y0 + h, x0:x0 + w])


def pack_label(labels, width: int) -> np.ndarray:
    """Zero-pad/truncate a label vector to ``label_width`` entries."""
    out = np.zeros((width,), np.float32)
    w = min(width, len(labels))
    out[:w] = labels[:w]
    return out


def _ri(rng, *args):
    """randint across both numpy RNG APIs: the per-item decode rng is a
    ``np.random.Generator`` (PCG64 — ~8x cheaper to construct per item
    than RandomState, which costs ~0.18 ms each at one per image), while
    long-lived callers (iter_img, mean computation) still pass
    RandomState. Same [lo, hi) semantics on both."""
    f = getattr(rng, "integers", None)
    return f(*args) if f is not None else rng.randint(*args)


class ImageAugmenter:
    """Affine + crop + photometric augmentation of one HWC float image."""

    def __init__(self, p: AugmentParams, out_shape: Tuple[int, int, int]):
        self.p = p
        self.out_c, self.out_y, self.out_x = out_shape

    def _affine(self, img: np.ndarray, rng: RngLike) -> np.ndarray:
        import cv2
        p = self.p
        if p.rotate_list:
            angle = float(p.rotate_list[_ri(rng, len(p.rotate_list))])
        elif p.rotate >= 0:
            angle = float(p.rotate)
        else:
            angle = rng.uniform(-p.max_rotate_angle, p.max_rotate_angle)
        a = angle * np.pi / 180.0
        # aspect/shear jitter on top of rotation (image_augmenter-inl.hpp:70-150)
        ratio = 1.0 + rng.uniform(-p.max_aspect_ratio, p.max_aspect_ratio) \
            if p.max_aspect_ratio > 0 else 1.0
        shear = rng.uniform(-p.max_shear_ratio, p.max_shear_ratio) \
            if p.max_shear_ratio > 0 else 0.0
        h, w = img.shape[:2]
        if p.min_crop_size > 0 and p.max_crop_size + 1 > p.min_crop_size:
            crop = _ri(rng, p.min_crop_size, p.max_crop_size + 1)
            scale = float(self.out_y) / crop
        else:
            scale = rng.uniform(p.min_random_scale, p.max_random_scale)
        # Bound the effective content scale so the scaled image size stays in
        # [min_img_size, max_img_size]. Intentional semantic difference from
        # the reference (image_augmenter-inl.hpp:92-94), which clamps the
        # warp canvas size while keeping content scale: here the affine
        # renders straight into the output crop, so the size bound is
        # expressed as a scale bound instead.
        hscale = np.clip(scale * h, p.min_img_size, p.max_img_size) / h
        wscale = np.clip(scale * w, p.min_img_size, p.max_img_size) / w
        hs, ws = hscale * ratio, wscale / max(ratio, 1e-8)
        cos_a, sin_a = np.cos(a), np.sin(a)
        m = np.array([[cos_a * ws, (sin_a + shear) * hs, 0.0],
                      [-sin_a * ws, (cos_a + shear) * hs, 0.0]], np.float32)
        m[0, 2] = self.out_x / 2.0 - (m[0, 0] * w / 2.0 + m[0, 1] * h / 2.0)
        m[1, 2] = self.out_y / 2.0 - (m[1, 0] * w / 2.0 + m[1, 1] * h / 2.0)
        fv = float(self.p.fill_value)
        return cv2.warpAffine(
            img, m, (self.out_x, self.out_y), flags=cv2.INTER_LINEAR,
            borderMode=cv2.BORDER_CONSTANT, borderValue=(fv, fv, fv))

    def _crop(self, img: np.ndarray, rng: RngLike) -> np.ndarray:
        """Random/center/fixed crop to (out_y, out_x)
        (iter_augment_proc-inl.hpp:60-140)."""
        h, w = img.shape[:2]
        oy, ox = self.out_y, self.out_x
        if h == oy and w == ox:
            return img
        if h < oy or w < ox:     # upscale small images to cover the crop
            import cv2
            s = max(oy / h, ox / w)
            img = cv2.resize(img, (max(ox, int(w * s + 0.5)),
                                   max(oy, int(h * s + 0.5))),
                             interpolation=cv2.INTER_LINEAR)
            h, w = img.shape[:2]
        p = self.p
        if p.rand_crop:
            y0 = _ri(rng, 0, h - oy + 1)
            x0 = _ri(rng, 0, w - ox + 1)
        elif p.crop_y_start >= 0 or p.crop_x_start >= 0:
            y0 = max(p.crop_y_start, 0)
            x0 = max(p.crop_x_start, 0)
        else:
            y0, x0 = (h - oy) // 2, (w - ox) // 2
        return img[y0:y0 + oy, x0:x0 + ox]

    def process_u8(self, img: np.ndarray,
                   rng: RngLike):
        """uint8-exact fast path for the device_normalize pipeline:
        crop + mirror without the float32 round-trip (process() costs
        five full-image passes — float cast, contiguous copy, rint,
        clip, uint8 cast — ~0.5 ms/img of the 1-core host budget;
        crop/mirror are pure slicing on uint8). Returns None when the
        image needs the float path (affine/contrast/illumination
        configured, non-uint8 input, or an upscale — whose float
        interpolation must round exactly like process()+rint); RNG draw
        order matches process() exactly, so falling between paths never
        shifts the augmentation stream."""
        if (self.p.needs_affine or self.p.max_random_contrast > 0
                or self.p.max_random_illumination > 0
                or img.dtype != np.uint8):
            return None
        if img.ndim == 2:
            img = img[:, :, None]
        if img.shape[0] < self.out_y or img.shape[1] < self.out_x:
            return None                       # resize: float path rounds
        cropped = self._crop(img, rng)
        if (self.p.rand_mirror and _ri(rng, 2)) or self.p.mirror:
            cropped = cropped[:, ::-1]
        if img.nbytes > 2 * cropped.nbytes:
            # a view would pin the full decoded image in the ~4x-batch
            # item buffer; copy when the crop keeps only a fraction of it
            return np.ascontiguousarray(cropped)
        # near-full-frame crop: return the VIEW — the batch assembler's
        # np.stack makes the one contiguous copy, and a per-image
        # ascontiguousarray here would double the copies (~0.2 ms/img)
        return cropped

    def process(self, img: np.ndarray,
                rng: RngLike) -> np.ndarray:
        """HWC uint8/float in, (out_y, out_x, C) float32 out (pre-mean)."""
        img = np.asarray(img, np.float32)
        if img.ndim == 2:
            img = img[:, :, None]
        if self.p.needs_affine:
            img = self._affine(img, rng)
            if img.ndim == 2:
                img = img[:, :, None]
        img = self._crop(img, rng)
        if (self.p.rand_mirror and _ri(rng, 2)) or self.p.mirror:
            img = img[:, ::-1]
        p = self.p
        if p.max_random_contrast > 0 or p.max_random_illumination > 0:
            c = 1.0 + rng.uniform(-p.max_random_contrast,
                                  p.max_random_contrast)
            b = rng.uniform(-p.max_random_illumination,
                            p.max_random_illumination)
            img = img * c + b
        return np.ascontiguousarray(img, np.float32)


class MeanStore:
    """Mean-image subtraction with on-the-fly computation + .npy caching
    (reference CreateMeanImg, iter_augment_proc-inl.hpp:175-205; the cache
    format here is numpy's, not mshadow's)."""

    def __init__(self, path: str, shape_hwc: Tuple[int, int, int]):
        self.path = path
        self.shape = shape_hwc
        self.mean: Optional[np.ndarray] = None
        from . import stream
        if path and stream.exists(path):
            if path.endswith(".binaryproto"):
                # Caffe mean import (VERDICT r5 #6): parse the BlobProto
                # at the wire level, BGR->RGB, center-crop the (usually
                # resize-sized) mean to the input crop
                with stream.sopen(path, "rb") as f:
                    mean = load_binaryproto_mean(f.read())
                self.mean = _center_crop_mean(mean, shape_hwc)
            else:
                with stream.sopen(path, "rb") as f:
                    self.mean = np.load(f)

    @property
    def ready(self) -> bool:
        return self.mean is not None

    def compute(self, images) -> None:
        """images: iterable of (out_y, out_x, c) float arrays."""
        if self.path.endswith(".binaryproto"):
            raise ValueError(
                f"mean file {self.path!r} not found; .binaryproto means "
                "are imported, never computed — convert with "
                "tools/import_caffe.py --mean or point image_mean at a "
                ".npy path")
        acc = np.zeros(self.shape, np.float64)
        n = 0
        for im in images:
            acc += im
            n += 1
        self.mean = (acc / max(n, 1)).astype(np.float32)
        if self.path:
            from . import stream
            with stream.sopen(self.path, "wb") as f:
                np.save(f, self.mean)

    def apply(self, img: np.ndarray, p: AugmentParams) -> np.ndarray:
        if p.mean_value is not None:
            img = img - p.mean_value
        elif self.mean is not None:
            img = img - self.mean
        if p.divideby != 1.0:
            img = img * (1.0 / p.divideby)
        if p.scale != 1.0:
            img = img * p.scale
        return img
