"""Image record iterator: sharded reads + parallel decode + augmentation.

Reference analog: ImageRecordIOIterator + ImageRecordIOParser
(/root/reference/src/io/iter_image_recordio-inl.hpp:92-333) — the modern
imgrec path: dmlc::InputSplit chunked reads sharded by (rank, nworkers),
OpenMP parallel jpeg decode, in-chunk shuffle, ThreadedIter prefetch. Here
the same pipeline is a chunked RecordReader + a thread pool for decode
(optionally the native C++ decoder when built) + numpy augmentation,
wrapped by the generic threadbuffer iterator for prefetch.

Also registers ``imgbin``/``imgbinx``/``imginst``/``imgbinold`` as aliases:
the legacy BinaryPage formats collapse into recordio in this framework
(tools/im2rec converts; see tools/ for the packer).
"""

from __future__ import annotations

import concurrent.futures as futures
import io as _io
import os
from typing import List, Optional

import numpy as np

from .data import DataBatch, DataIter, register_iter
from .recordio import ImageRecord, RecordReader, read_image_list
from .augment import (AugmentParams, ImageAugmenter, MeanStore,
                      mean_cache_path, pack_label)


#: the "which decoder" line prints once per process
_DECODER_SAID = False


def decode_image(data: bytes, want_channels: int = 3) -> np.ndarray:
    """Decode jpeg/png bytes to HWC uint8 (RGB, or single-channel luma when
    ``want_channels == 1``) via the native decoder (io/native.py builds it
    on first use), else cv2/PIL where it cannot be built.
    Raw float tensors (flag==1 records) skip this."""
    from . import native
    arr = native.try_decode(data, want_channels)
    if arr is not None:
        return arr
    gray = want_channels == 1
    try:
        import cv2
        flag = cv2.IMREAD_GRAYSCALE if gray else cv2.IMREAD_COLOR
        a = cv2.imdecode(np.frombuffer(data, np.uint8), flag)
        if a is None:
            raise ValueError("cv2.imdecode failed")
        return a[:, :, None] if gray else a[:, :, ::-1]      # BGR -> RGB
    except ImportError:
        from PIL import Image
        img = Image.open(_io.BytesIO(data)).convert("L" if gray else "RGB")
        a = np.asarray(img)
        return a[:, :, None] if gray else a


def expand_conf_files(prefix: str, ids: str, rank: int, nworker: int):
    """Expand ``image_conf_prefix``/``image_conf_ids`` into this worker's
    (bin, lst) file pairs (reference iter_thread_imbin_x-inl.hpp:113-150):
    ids is an inclusive range 'lb-ub', each id formats the printf-style
    prefix, and workers take contiguous chunks of ceil(n/nworker) files."""
    import re
    m = re.match(r"^(-?\d+)-(-?\d+)$", ids.strip())
    if not m:
        raise ValueError(
            f"image_conf_ids only supports a range like 1-100, got {ids!r}")
    lb, ub = int(m.group(1)), int(m.group(2))
    n = ub + 1 - lb
    if n <= 0:
        raise ValueError(f"image_conf_ids: empty range {ids!r}")
    # validate the formatting over the FULL id range before worker slicing
    # (a per-worker check could see one name and miss that every worker
    # resolves to the same file)
    try:
        all_names = [prefix % i for i in range(lb, ub + 1)]
    except (TypeError, ValueError) as e:
        raise ValueError(
            f"image_conf_prefix must contain a printf-style integer "
            f"placeholder (e.g. 'part%03d'), got {prefix!r}: {e}") from e
    if n > 1 and len(set(all_names)) != len(all_names):
        raise ValueError(
            f"image_conf_prefix {prefix!r} does not vary with "
            "image_conf_ids — missing a %d placeholder?")
    if nworker > 1:
        step = (n + nworker - 1) // nworker
        begin = min(rank * step, n)
        end = min((rank + 1) * step, n)
        if begin >= end:
            raise ValueError(
                "image_conf: too many workers — the id list cannot be "
                "divided between them")
        all_names = all_names[begin:end]
    return [(name + ".bin", name + ".lst") for name in all_names]


@register_iter("imgrec", "imgbin", "imgbinx", "imginst", "imgbinold")
class ImageRecordIterator(DataIter):
    """Batched, augmented, sharded image-record reader."""

    supports_dist_shard = True

    def set_param(self, name, val):
        if name in ("image_rec", "path_imgrec"):
            self.rec_path = val
        elif name in ("image_bin", "path_imgbin"):
            # legacy BinaryPage pack (reference iter_thread_imbin); labels
            # come from the k-th line of image_list
            self.bin_path = val
        elif name in ("image_list", "path_imglist"):
            self.list_path = val
        elif name == "image_conf_prefix":
            # printf-style template for multi-file BinaryPage packs
            # (reference iter_thread_imbin_x-inl.hpp:113-150): each id in
            # image_conf_ids expands to <prefix%id>.bin/.lst
            self.conf_prefix = val
        elif name == "image_conf_ids":
            self.conf_ids = val
        elif name == "batch_size":
            self.batch_size = int(val)
        elif name == "input_shape":
            self.input_shape = tuple(int(x) for x in val.split(","))
        elif name == "shuffle":
            self.shuffle = int(val)
        elif name == "seed_data":
            self.seed = int(val)
        elif name == "label_width":
            self.label_width = int(val)
        elif name == "round_batch":
            self.round_batch = int(val)
        elif name == "dist_num_worker":
            self.nworker = int(val)
        elif name == "dist_worker_rank":
            self.rank = int(val)
        elif name == "decode_threads":
            self.nthread = int(val)
        elif name == "silent":
            self.silent = int(val)
        else:
            self.aug.set_param(name, val)

    def __init__(self, cfg):
        self.rec_path = ""
        self.bin_path = ""
        self.list_path = ""
        self.conf_prefix = ""
        self.conf_ids = ""
        self.batch_size = 128
        self.input_shape = None
        self.shuffle = 0
        self.seed = 0
        self.label_width = 1
        self.round_batch = 0
        self.nworker = int(os.environ.get("CXXNET_NUM_WORKER", "1"))
        self.rank = int(os.environ.get("CXXNET_WORKER_RANK",
                                       os.environ.get("PS_RANK", "0")))
        self.nthread = min(8, os.cpu_count() or 4)
        self.silent = 0
        self.aug = AugmentParams()
        super().__init__(cfg)

    # -- setup -------------------------------------------------------------
    def init(self):
        if self.conf_prefix:
            if self.rec_path or self.bin_path or self.list_path:
                raise ValueError(
                    "set either image_conf_prefix or image_bin/image_list, "
                    "not both (reference iter_thread_imbin_x-inl.hpp:124)")
            self._conf_pairs = expand_conf_files(
                self.conf_prefix, self.conf_ids, self.rank, self.nworker)
            if self.round_batch and self.nworker > 1:
                self._check_conf_batch_counts()
        elif not self.rec_path and not self.bin_path:
            raise ValueError("imgrec: image_rec (or image_bin) must be set")
        elif self.round_batch and self.nworker > 1:
            self._check_shard_batch_counts()
        if self.bin_path and not self.list_path:
            raise ValueError("imgbin: image_list must accompany image_bin "
                             "(labels live in the list)")
        if self.input_shape is None:
            raise ValueError("imgrec: input_shape must be set")
        c, y, x = self.input_shape
        self.augmenter = ImageAugmenter(self.aug, (c, y, x))
        self.mean = MeanStore(mean_cache_path(self.aug), (y, x, c))
        self._label_map = None
        self._list_entries = None
        if self.list_path:
            self._list_entries = read_image_list(self.list_path)   # once
            self._label_map = {idx: lab for idx, lab, _
                               in self._list_entries}
        if self.aug.device_normalize == -1:
            # auto-resolve: uint8 H2D (4x smaller transfer + on-device
            # normalize) is the production default whenever it is exact —
            # crop/mirror keep uint8 pixels. Fall back to the host float
            # path for float-producing augmentations (affine/contrast/
            # illumination), raw float-tensor records (flag==1), and
            # images smaller than the crop (the upscale interpolates).
            # The size check samples the shard's first few records (not
            # just one — a large first image must not hide sub-crop-size
            # ones behind it and silently switch the default's numerics);
            # datasets mixing sizes deeper than the probe should set
            # device_normalize=0 explicitly.
            exact = (not self.aug.needs_affine
                     and self.aug.max_random_contrast == 0
                     and self.aug.max_random_illumination == 0)
            if exact:
                for rec in self._peek_records(8):
                    if rec.flag != 0:
                        exact = False
                        break
                    img = self._decode(rec)
                    _, y, x = self.input_shape
                    if img.shape[0] < y or img.shape[1] < x:
                        exact = False
                        break
            self.aug.device_normalize = int(exact)
            if not self.silent:
                print(f"imgrec: device_normalize auto-resolved to "
                      f"{self.aug.device_normalize} "
                      f"({'uint8 device path' if exact else 'host float path'})")
        global _DECODER_SAID
        if not self.silent and not _DECODER_SAID:
            # once per process: a run that quietly lost its native
            # decoder decodes several times slower
            _DECODER_SAID = True
            from . import native
            print(f"imgrec: decoding with {native.decoder_name()}",
                  flush=True)
        self._pool = futures.ThreadPoolExecutor(self.nthread)
        self._rng = np.random.RandomState(self.seed + 7 * self.rank)
        # monotonically increasing per-item augmentation counter, hashed
        # before seeding so streams are deterministic under any thread-pool
        # schedule yet uncorrelated across seeds/ranks
        self._item_counter = (self.seed << 32) ^ (self.rank << 56)
        if self.aug.mean_img and not self.mean.ready:
            self._compute_mean()
        self.before_first()


    def _check_conf_batch_counts(self) -> None:
        """Whole-file conf-prefix sharding gives each rank ceil(shard/batch)
        batches; when shards are uneven enough that those counts differ,
        round_batch CANNOT equalize epochs and every jitted update would
        deadlock on a missing rank. Fail fast at init (counting .lst lines
        is cheap and the lists are on the shared filesystem)."""
        counts = []
        for rank in range(self.nworker):
            pairs = expand_conf_files(self.conf_prefix, self.conf_ids,
                                      rank, self.nworker)
            n = sum(len(read_image_list(lst)) for _, lst in pairs)
            counts.append(-(-n // self.batch_size))      # ceil
        if len(set(counts)) != 1:
            raise ValueError(
                "image_conf_prefix + round_batch: per-rank batch counts "
                f"{counts} are unequal — whole-file sharding cannot give "
                "every worker the same epoch length with these pack sizes; "
                "re-pack into equal-size parts (tools/im2bin.py) or use a "
                "single recordio file (byte-range sharded)")

    def _peek_records(self, n: int) -> List[ImageRecord]:
        """First ``n`` records of this worker's shard (fewer for a short
        shard) — init-time probe for the device_normalize auto-resolution."""
        reader = self._reader()
        out: List[ImageRecord] = []
        try:
            for payload in reader:
                out.append(ImageRecord.unpack(payload))
                if len(out) >= n:
                    break
        finally:
            close = getattr(reader, "close", None)
            if close is not None:
                close()
        return out

    def _check_shard_batch_counts(self) -> None:
        """round_batch promises every rank the same number of batches per
        epoch (each rank emits ceil(shard/batch), wrapping its own shard) —
        but byte-range recordio shards and round-robin binpage page shards
        can hold unequal record counts, and if the per-rank ceil counts
        differ every rank's jitted update deadlocks waiting on a missing
        peer. Fail fast at init with a header-only count (payload bytes are
        never read)."""
        if self.bin_path:
            from .binpage import num_pages, page_object_count
            per_page = [page_object_count(self.bin_path, p)
                        for p in range(num_pages(self.bin_path))]
            recs = [sum(per_page[r::self.nworker])
                    for r in range(self.nworker)]
        else:
            from .recordio import shard_record_counts
            recs = shard_record_counts(self.rec_path, self.nworker)
        counts = [-(-n // self.batch_size) for n in recs]      # ceil
        if len(set(counts)) != 1:
            raise ValueError(
                f"round_batch with {self.nworker} workers: per-rank batch "
                f"counts {counts} (record counts {recs}) are unequal — "
                "every rank must emit the same epoch length or distributed "
                "training deadlocks; re-pack with tools/im2rec.py "
                "(uniform record sizes shard evenly) or adjust batch_size")

    def _reader(self):
        """Iterable of packed ImageRecord payloads: recordio, a legacy
        BinaryPage pack re-wrapped on the fly (k-th object pairs with the
        k-th image_list line for inst_id/label), or this worker's slice of
        a multi-file conf-prefix pack set."""
        if self.conf_prefix:
            from .binpage import iter_binpage

            def gen_multi():
                for bin_path, lst_path in self._conf_pairs:
                    entries = read_image_list(lst_path)
                    # file-level partitioning only: each worker owns whole
                    # files, so no intra-file (rank, nworker) split here
                    for obj_idx, data in iter_binpage(bin_path, 0, 1):
                        inst_id, labels, _ = entries[obj_idx]
                        yield ImageRecord(inst_id=inst_id, labels=labels,
                                          data=data).pack()
            return gen_multi()
        if not self.bin_path:
            return RecordReader(self.rec_path, self.rank, self.nworker)
        from .binpage import iter_binpage
        entries = self._list_entries          # parsed once in init()

        def gen():
            for obj_idx, data in iter_binpage(self.bin_path, self.rank,
                                              self.nworker):
                inst_id, labels, _ = entries[obj_idx]
                yield ImageRecord(inst_id=inst_id, labels=labels,
                                  data=data).pack()
        return gen()

    def _compute_mean(self):
        if not self.silent:
            print(f"computing mean image from {self.rec_path} ...")
        rng = np.random.RandomState(0)
        def gen():
            for payload in self._reader():
                rec = ImageRecord.unpack(payload)
                yield self.augmenter.process(
                    self._decode(rec), rng)
        self.mean.compute(gen())

    def _decode(self, rec: ImageRecord) -> np.ndarray:
        c, y, x = self.input_shape
        if rec.flag == 1:    # raw float tensor record
            return np.frombuffer(rec.data, np.float32).reshape(y, x, c)
        return decode_image(rec.data, c)

    # -- iteration ---------------------------------------------------------
    def before_first(self):
        self._iter = iter(self._reader())
        self._buf: List = []
        self._done = False

    @staticmethod
    def _hash_seed(counter: int) -> int:
        """splitmix64-style integer mix so consecutive counters (and
        shifted seed/rank bases) yield uncorrelated RNG streams. Full
        64-bit output — PCG64 takes it whole; the old 31-bit truncation
        (a RandomState seed-range limit) would birthday-collide hundreds
        of item pairs per ImageNet-scale epoch."""
        z = (counter + 0x9E3779B97F4A7C15) & 0xFFFFFFFFFFFFFFFF
        z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & 0xFFFFFFFFFFFFFFFF
        z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & 0xFFFFFFFFFFFFFFFF
        return z ^ (z >> 31)

    def _process_one(self, payload: bytes, item_counter: int):
        rec = ImageRecord.unpack(payload)
        # Generator(PCG64) rather than RandomState: ~8x cheaper to build
        # (~23 us vs ~180 us), and one is built per image — RandomState
        # construction alone was ~13% of the host input budget
        rng = np.random.Generator(
            np.random.PCG64(self._hash_seed(item_counter)))
        if self.aug.device_normalize:
            # defer mean/divideby/scale to the device (trainer applies them
            # after a 4x smaller uint8 host->device copy); crop/mirror
            # stay pure uint8 slicing (process_u8 — no float round-trip),
            # float-producing augmentations (affine/contrast/upscale)
            # take the float path and round to the nearest LSB
            decoded = self._decode(rec)
            img = self.augmenter.process_u8(decoded, rng)
            if img is None:
                img = self.augmenter.process(decoded, rng)
                img = np.clip(np.rint(img), 0.0, 255.0).astype(np.uint8)
        else:
            img = self.augmenter.process(self._decode(rec), rng)
            img = self.mean.apply(img, self.aug)
        if self._label_map is not None and rec.inst_id in self._label_map:
            lab = self._label_map[rec.inst_id]
        else:
            lab = rec.labels
        return img, pack_label(lab, self.label_width), rec.inst_id

    def _decode_raw(self, raw):
        """Decode a list of packed payloads on the pool with fresh
        deterministic per-item seeds."""
        seeds = range(self._item_counter, self._item_counter + len(raw))
        self._item_counter += len(raw)
        return list(self._pool.map(self._process_one, raw, seeds))

    def _fill(self, n: int) -> None:
        """Read up to n raw records, decode them on the pool."""
        raw = []
        for payload in self._iter:
            raw.append(payload)
            if len(raw) >= n:
                break
        if len(raw) < n:
            self._done = True
        if self.shuffle:
            self._rng.shuffle(raw)
        self._buf.extend(self._decode_raw(raw))

    def _wrap_fill(self, n: int):
        """Decode the first ``n`` records of this worker's shard again —
        round_batch wraparound (reference iter_batch_proc-inl.hpp:85-99):
        every rank emits ceil(shard/batch) full batches per epoch, with the
        wrapped duplicates counted as padding so loss/metrics exclude them."""
        reader = self._reader()
        raw = []
        try:
            for payload in reader:
                raw.append(payload)
                if len(raw) >= n:
                    break
        finally:
            close = getattr(reader, "close", None)
            if close is not None:
                close()
        return self._decode_raw(raw)

    def next(self) -> Optional[DataBatch]:
        bs = self.batch_size
        if not self._done and len(self._buf) < bs:
            # decode a few batches ahead so shuffle mixes across batches
            self._fill(bs * 4)
        if not self._buf:
            return None
        take = self._buf[:bs]
        self._buf = self._buf[bs:]
        padd = 0
        if len(take) < bs:
            padd = bs - len(take)
            if self.round_batch:
                take = take + self._wrap_fill(padd)
            if len(take) < bs:          # shard smaller than the shortfall
                take = take + [take[-1]] * (bs - len(take))
        data = np.stack([t[0] for t in take])
        label = np.stack([t[1] for t in take])
        index = np.asarray([t[2] for t in take], np.int64)
        norm = None
        if self.aug.device_normalize:
            # same precedence and op order as the host path
            # (MeanStore.apply): mean_value wins over the mean image, then
            # divideby, then scale
            mean = (self.aug.mean_value if self.aug.mean_value is not None
                    else (self.mean.mean if self.mean.ready else None))
            norm = {"mean": mean, "divideby": self.aug.divideby,
                    "scale": self.aug.scale}
        return DataBatch(data=data, label=label, num_batch_padd=padd,
                         inst_index=index, norm=norm)
