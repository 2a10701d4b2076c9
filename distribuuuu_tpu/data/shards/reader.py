"""ShardDataset: streaming reader over a packed shard split.

Same dataset surface the loader already speaks for imagefolder —
``__len__``/``__getitem__``/``load_batch``/``set_epoch_seed``/``classes`` —
so the whole downstream stack (thread-pool assembly, retry/skip
resilience, device prefetch ring, device-normalize) is reused unchanged.
What differs is underneath: samples come from a handful of large shard
files via positioned reads (``os.pread`` — lockless under the loader's
worker threads) instead of one ``open()`` per JPEG, and the train-time
sample order is the window-shuffled sequential order of ``order.py``
(:meth:`make_sampler`), so reads track a sequential sweep.

Decode parity: records hold the source files' encoded bytes verbatim, and
augmentation randomness is the same ``(base_seed, epoch, idx)``-derived
stream the imagefolder dataset draws — sample i of a packed split decodes
byte-identically to sample i of the source tree (packing preserves scan
order). The native C++ kernel decodes straight from the record buffers
(``native.load_batch_mem``); PIL covers fallback and exotic formats.

Failure containment: a damaged record (CRC mismatch, truncation-lost
tail) raises ``ShardReadError`` from exactly one sample; the loader's
``DATA.RETRIES``/``DATA.SKIP_CORRUPT`` machinery substitutes and logs it.
A shard whose index footer is gone is re-indexed by forward scan at open
(warned, with the recovered/lost record counts) — the
``FAULTS.TRUNCATE_SHARD`` injection drills exactly this path.
"""

from __future__ import annotations

import io
import os
import threading

import numpy as np

from distribuuuu_tpu.data.shards.format import (
    ShardReadError,
    read_record_at,
    read_shard_index,
    read_shard_manifest,
)
from distribuuuu_tpu.data.transforms import train_transform, val_transform


class RecordShards:
    """The species-independent half of a shard reader: manifest load,
    global-index→(shard, record) mapping, lazy per-shard fd + index, and
    the lockless positioned record read. :class:`ShardDataset` (images)
    and the token species (data/shards/tokens.TokenShardDataset) both
    stream through exactly this core, so footer recovery, the
    ``ShardReadError``→``DATA.SKIP_CORRUPT`` containment path, and the
    shard-IO telemetry tallies are one implementation."""

    FORMAT = "shards"
    # the manifest species this reader decodes (absence in an old image
    # manifest reads as "images")
    KIND = "images"

    def _open_split(self, root: str, split: str) -> None:
        from distribuuuu_tpu.data.shards.format import ShardFormatError
        from distribuuuu_tpu.utils import faults

        self.dir = os.path.join(root, split)
        faults.maybe_truncate_shard(self.dir)  # injection no-op (FAULTS.*)
        self.manifest = read_shard_manifest(self.dir)
        kind = self.manifest.get("kind", "images")
        if kind != self.KIND:
            raise ShardFormatError(
                f"{self.dir} holds {kind!r} shards but DATA.FORMAT selects "
                f"the {self.KIND!r} reader — point TRAIN/TEST.DATASET at a "
                f"{self.KIND} pack ("
                + ("tools/make_shards.py" if self.KIND == "images"
                   else "tools/make_token_shards.py")
                + " writes one) or switch DATA.FORMAT"
            )
        self._shards = self.manifest["shards"]
        # global index i → shard s where cum[s] <= i < cum[s+1]
        counts = [int(s["records"]) for s in self._shards]
        self._cum = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
        self._n = int(self.manifest["num_records"])
        # per-shard fd + offsets, opened/indexed lazily under a lock (the
        # pread calls themselves are lockless and thread-safe)
        self._open_lock = threading.Lock()
        self._fds: dict[int, int] = {}
        self._offsets: dict[int, list[int]] = {}

    # ------------------------------------------------------------- plumbing
    def _shard_of(self, idx: int) -> tuple[int, int]:
        if not 0 <= idx < self._n:
            raise IndexError(f"sample {idx} out of range [0, {self._n})")
        s = int(np.searchsorted(self._cum, idx, side="right")) - 1
        return s, idx - int(self._cum[s])

    def _ensure_open(self, s: int) -> tuple[int, list[int]]:
        with self._open_lock:
            if s not in self._fds:
                from distribuuuu_tpu.utils.logger import get_logger

                path = os.path.join(self.dir, self._shards[s]["file"])
                offsets, recovered = read_shard_index(path)
                expect = int(self._shards[s]["records"])
                if recovered or len(offsets) != expect:
                    get_logger().warning(
                        "shard %s: index footer unreadable — recovered %d of "
                        "%d records by forward scan; lost records will raise "
                        "and flow through the DATA.SKIP_CORRUPT path",
                        path, len(offsets), expect,
                    )
                self._fds[s] = os.open(path, os.O_RDONLY)
                self._offsets[s] = offsets
            return self._fds[s], self._offsets[s]

    def record(self, idx: int) -> tuple[bytes, int, str]:
        """Raw record ``(image_bytes, label, key)`` — the byte-identical
        round-trip surface (tests) and the decode input."""
        s, r = self._shard_of(int(idx))
        fd, offsets = self._ensure_open(s)
        if r >= len(offsets):
            raise ShardReadError(
                f"sample {idx}: record {r} of {self._shards[s]['file']} lost "
                f"to truncation (shard has {len(offsets)} readable records, "
                f"manifest says {self._shards[s]['records']})"
            )
        return read_record_at(fd, offsets[r], self._shards[s]["file"])

    def close(self) -> None:
        with self._open_lock:
            for fd in self._fds.values():
                os.close(fd)
            self._fds.clear()
            self._offsets.clear()

    # ------------------------------------------- shared loader surface
    def __len__(self):
        return self._n

    def set_epoch_seed(self, seed: int) -> None:
        self._epoch_seed = seed

    def make_sampler(self, num_replicas: int, rank: int, shuffle: bool,
                     seed: int, drop_last: bool = False):
        """The loader's sampler hook: train (shuffle) gets the
        window-shuffled sequential order; val returns None → the plain
        DistributedSampler (storage order — already sequential). Shared by
        both species — which is what carries exact mid-epoch resume to the
        token pipeline for free (the cursor protocol only needs
        ``order_state``)."""
        if not shuffle:
            return None
        from distribuuuu_tpu.config import cfg
        from distribuuuu_tpu.data.shards.order import WindowShuffleSampler

        return WindowShuffleSampler(
            self._n, num_replicas, rank, seed=seed,
            block=int(cfg.DATA.SHARDS_BLOCK),
            window=int(cfg.DATA.SHARDS_WINDOW),
            drop_last=drop_last,
        )


class ShardDataset(RecordShards):
    """The IMAGE shard species: encoded image bytes per record, decoded
    through PIL or the C++ kernel's memory-buffer API (module docstring)."""

    def __init__(
        self,
        root: str,
        split: str,
        im_size: int,
        train: bool,
        base_seed: int = 0,
        crop_size: int | None = None,
        backend: str = "auto",
        raw_u8: bool = False,
    ):
        self._open_split(root, split)
        self.classes = list(self.manifest["classes"])
        self.im_size = im_size
        self.crop_size = im_size if crop_size is None else crop_size
        self.train = train
        self.base_seed = base_seed
        self._epoch_seed = 0
        if backend not in ("auto", "native", "pil"):
            raise ValueError(f"DATA.BACKEND must be auto|native|pil, got {backend}")
        self.backend = backend
        self.raw_u8 = raw_u8

    def _rng(self, idx: int) -> np.random.Generator:
        # identical stream to ImageFolderDataset._rng — same (seed, epoch,
        # sample) triple, so a packed corpus augments byte-identically
        return np.random.default_rng(
            np.random.SeedSequence([self.base_seed, self._epoch_seed, idx])
        )

    def _use_native(self) -> bool:
        if self.backend == "pil":
            return False
        from distribuuuu_tpu import native

        if native.available() and native.has_mem_api():
            return True
        if self.backend == "native":
            raise RuntimeError(
                "DATA.BACKEND=native but the C++ kernel (with the memory-"
                f"buffer API shards need) is unavailable: {native.build_error()}"
            )
        return False

    def _decode_pil(self, image_bytes: bytes, idx: int) -> np.ndarray:
        from PIL import Image

        with Image.open(io.BytesIO(image_bytes)) as img:
            img = img.convert("RGB")
            if self.train:
                return train_transform(
                    img, self.im_size, self._rng(idx), normalize=not self.raw_u8
                )
            return val_transform(
                img, self.im_size, self.crop_size, normalize=not self.raw_u8
            )

    def __getitem__(self, idx: int):
        image_bytes, label, _ = self.record(int(idx))
        return self._decode_pil(image_bytes, int(idx)), label

    def load_batch(self, idxs, n_threads: int = 4):
        """Batch decode from record buffers — the C++ kernel path
        (``native.load_batch_mem``: one GIL-free call, internal thread
        pool) with per-image PIL fallback, mirroring the imagefolder
        dataset's contract. Returns ``(images, labels)``."""
        out_size = self.im_size if self.train else self.crop_size
        recs = [self.record(int(i)) for i in idxs]
        labels = np.asarray([r[1] for r in recs], np.int32)
        out_dtype = np.uint8 if self.raw_u8 else np.float32
        if not self._use_native():
            images = np.stack([
                self._decode_pil(rec[0], int(i)) for rec, i in zip(recs, idxs)
            ])
            return images.astype(out_dtype), labels

        from distribuuuu_tpu import native
        from distribuuuu_tpu.data import transforms as T

        n = len(recs)
        geoms = np.zeros((n,), native.GEOM_DTYPE)
        bufs: list[bytes] = []
        fallback: list[int] = []
        for pos, (rec, idx) in enumerate(zip(recs, (int(i) for i in idxs))):
            dims = native.mem_dims(rec[0])
            if dims is None:  # exotic format → PIL for this image
                bufs.append(b"")  # sentinel: C++ fails it instantly
                fallback.append(pos)
                continue
            bufs.append(rec[0])
            w, h = dims
            if self.train:
                g = T.train_geom(w, h, self.im_size, self._rng(idx))
            else:
                g = T.val_geom(w, h, self.im_size, self.crop_size)
            geoms[pos] = g + (0,)  # trailing struct padding field
        if self.raw_u8:
            images, statuses = native.load_batch_u8_mem(
                bufs, geoms, (out_size, out_size), n_threads,
            )
        else:
            images, statuses = native.load_batch_mem(
                bufs, geoms, (out_size, out_size),
                T.IMAGENET_MEAN, T.IMAGENET_STD, n_threads,
            )
        for pos in set(fallback) | set(np.nonzero(statuses)[0].tolist()):
            images[pos] = self._decode_pil(recs[pos][0], int(idxs[pos]))
        return images, labels
