"""Batch loader: per-host sharded iteration with background prefetch.

Mirror of the reference's DataLoader construction (ref:
/root/reference/distribuuuu/utils.py:121-184): train = shuffled sampler +
``drop_last=True``; val = unshuffled + ``drop_last=False``. The torch worker
pool becomes a thread pool assembling numpy batches ahead of the consumer;
device placement (the ``pin_memory``/``non_blocking`` analogue) happens in
the trainer via ``shard_batch`` with double-buffered async dispatch.

Each batch is a dict: ``image`` [B,H,W,C] float32 (NHWC — TPU-native),
``label`` [B] int32, ``mask`` [B] float32 (0 marks padding in the final
ragged eval batch, so metrics can ignore it in-graph; the reference instead
silently double-counts DistributedSampler's padded duplicates).
"""

from __future__ import annotations

import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np

from distribuuuu_tpu.config import cfg
from distribuuuu_tpu.data.dummy import DummyDataset
from distribuuuu_tpu.data.sampler import DistributedSampler
from distribuuuu_tpu.parallel import mesh as mesh_lib
from distribuuuu_tpu.telemetry import spans as telemetry_spans
from distribuuuu_tpu.utils import faults
from distribuuuu_tpu.utils.jsonlog import metrics_log
from distribuuuu_tpu.utils.logger import get_logger


class Loader:
    """Iterates a dataset as per-host batches using sampler shards."""

    def __init__(
        self,
        dataset,
        batch_size: int,
        shuffle: bool,
        drop_last: bool,
        workers: int = 4,
        seed: int = 0,
    ):
        self.dataset = dataset
        self.batch_size = batch_size
        self.drop_last = drop_last
        self.workers = max(1, workers)
        self._last_timing = None
        # Prefetch depth (batches assembled ahead of the consumer). When the
        # native backend is active each _assemble call already fans out over
        # `workers` C++ threads, so deep Python-side prefetch would multiply
        # to workers² decode threads; two in-flight batches suffice to
        # overlap. The PIL path decodes one image per Python thread, so there
        # the prefetch depth IS the parallelism.
        native_batch = False
        if hasattr(dataset, "_use_native"):
            try:
                native_batch = dataset._use_native()
            except RuntimeError:
                pass  # surfaces with a clear error at iteration time
        self.prefetch_depth = 2 if native_batch else self.workers
        # Decode resilience (DATA.RETRIES / RETRY_BACKOFF_S / SKIP_CORRUPT):
        # a failed decode retries with exponential backoff (transient
        # filesystem/network hiccups), then the corrupt sample is replaced
        # by a good one from the same batch and logged — one bad JPEG must
        # not abort a million-image epoch. SKIP_CORRUPT False = fail-stop.
        self.retries = max(0, int(cfg.DATA.RETRIES))
        self.retry_backoff = float(cfg.DATA.RETRY_BACKOFF_S)
        self.skip_corrupt = bool(cfg.DATA.SKIP_CORRUPT)
        # shard by DATA GROUP, not by process: processes sharing a data
        # row (model/pipe axes spanning hosts) must load identical data
        # (parallel/mesh.data_process_groups; ≡ (rank, world) in pure DP)
        data_rank, data_world = mesh_lib.data_process_groups()
        # Datasets may supply their own sampler (the shard reader's
        # window-shuffled sequential order, data/shards/order.py); the
        # torch-semantics DistributedSampler is the default. Both draw the
        # GLOBAL per-epoch order from (seed, epoch) alone and stride it by
        # rank, so k consumed global batches ≡ the order's first
        # k × global_batch entries on any topology — the invariant the
        # exact mid-epoch resume cursor (state_dict) rests on.
        self.sampler = None
        mk = getattr(dataset, "make_sampler", None)
        if mk is not None:
            self.sampler = mk(
                num_replicas=data_world, rank=data_rank, shuffle=shuffle,
                seed=seed, drop_last=False,
            )
        if self.sampler is None:
            self.sampler = DistributedSampler(
                len(dataset),
                num_replicas=data_world,
                rank=data_rank,
                shuffle=shuffle,
                seed=seed,
                drop_last=False,  # torch pads in the sampler; drop per-batch
            )
        self._epoch = 0
        self._resume: dict | None = None  # {"epoch", "skip"} — one-shot

    def set_epoch(self, epoch: int) -> None:
        self._epoch = epoch
        self.sampler.set_epoch(epoch)
        if hasattr(self.dataset, "set_epoch_seed"):
            self.dataset.set_epoch_seed(epoch)

    # ------------------------------------------------- exact mid-epoch resume
    def can_save_state(self) -> bool:
        """True when this loader's position is exactly resumable: the
        shard-format dataset plus an order whose identity is saveable
        (WindowShuffleSampler.order_state). The imagefolder path keeps the
        coarser epoch-granular resume."""
        return (
            getattr(self.dataset, "FORMAT", "") == "shards"
            and hasattr(self.sampler, "order_state")
        )

    def state_dict(self, batches_consumed: int) -> dict:
        """Saveable iterator state after ``batches_consumed`` batches of
        the current epoch: the epoch, the GLOBAL sample cursor (world-size
        independent — k global batches consume the order's first
        k × global_batch entries on any topology), and the shuffle-order
        identity incl. the shuffle-RNG state. JSON-able by construction;
        ``utils/checkpoint.save_preempt_checkpoint`` embeds it."""
        sd = {
            "v": 1,
            "format": getattr(self.dataset, "FORMAT", "imagefolder"),
            "epoch": int(self._epoch),
            "cursor": int(batches_consumed)
            * self.batch_size
            * self.sampler.num_replicas,
            "num_records": len(self.dataset),
        }
        if hasattr(self.sampler, "order_state"):
            sd["order"] = self.sampler.order_state()
        # dataset-species identity (the token pipeline's tokenizer/pack
        # fingerprint): a cursor must not survive a tokenizer or pack-len
        # change — the same byte stream would mean different tokens
        if hasattr(self.dataset, "identity"):
            sd["dataset_identity"] = self.dataset.identity()
        return sd

    def load_state_dict(self, sd: dict) -> int:
        """Arm the one-shot mid-epoch skip from a saved ``state_dict``.
        Returns the number of per-rank batches that will be skipped when
        the matching epoch is iterated. Raises ``ValueError`` when the
        cursor cannot be trusted (format/corpus/shuffle-identity changed)
        — the caller falls back to re-running the epoch from batch 0."""
        live_fmt = getattr(self.dataset, "FORMAT", "imagefolder")
        if sd.get("format") != live_fmt:
            raise ValueError(
                f"saved data state is {sd.get('format')!r}, live pipeline "
                f"is {live_fmt!r}"
            )
        if int(sd.get("num_records", -1)) != len(self.dataset):
            raise ValueError(
                f"corpus changed: saved {sd.get('num_records')} records, "
                f"live dataset has {len(self.dataset)}"
            )
        saved_order = sd.get("order")
        if saved_order is not None:
            if not hasattr(self.sampler, "order_state"):
                raise ValueError("live sampler has no saveable order")
            epoch = int(sd["epoch"])
            cur_epoch = self.sampler.epoch
            self.sampler.set_epoch(epoch)
            live_order = self.sampler.order_state()
            self.sampler.set_epoch(cur_epoch)
            if live_order != saved_order:
                diff = [
                    k for k in sorted(set(live_order) | set(saved_order))
                    if live_order.get(k) != saved_order.get(k)
                ]
                raise ValueError(
                    "shuffle order identity changed since the save "
                    f"(fields: {', '.join(diff)}) — the cursor would point "
                    "into a different permutation"
                )
        saved_ident = sd.get("dataset_identity")
        if saved_ident is not None:
            live_ident = (
                self.dataset.identity()
                if hasattr(self.dataset, "identity") else None
            )
            if live_ident != saved_ident:
                raise ValueError(
                    f"dataset identity changed since the save (saved "
                    f"{saved_ident}, live {live_ident}) — a tokenizer/"
                    "pack-len drift makes the cursor meaningless"
                )
        cursor = int(sd["cursor"])
        global_batch = self.batch_size * self.sampler.num_replicas
        skip, rem = divmod(cursor, global_batch)
        if rem:
            # topology grew (global batch no longer divides the cursor):
            # round DOWN — re-trains at most one partial batch, exactness
            # degrades to at-least-once for those samples (logged)
            get_logger().warning(
                "restored cursor %d is not a multiple of the live global "
                "batch %d — resuming at batch %d (up to %d samples re-run)",
                cursor, global_batch, skip, rem,
            )
        self._resume = {"epoch": int(sd["epoch"]), "skip": int(skip)}
        return int(skip)

    def resume_skip(self, epoch: int) -> int:
        """Batches the NEXT iteration of ``epoch`` will skip (armed by
        ``load_state_dict``; consumed one-shot by ``__iter__``)."""
        if self._resume is not None and self._resume["epoch"] == int(epoch):
            return self._resume["skip"]
        return 0

    def __len__(self):
        n = self.sampler.num_samples
        return n // self.batch_size if self.drop_last else -(-n // self.batch_size)

    def _assemble(self, idxs: np.ndarray, submit: float = 0.0) -> tuple:
        """Returns ``(batch, timing)``: the batch dict plus the stage
        timestamps of its assembly (utils/jsonlog.TIMELINE_STAGES subset:
        submit/dec0/dec1/asm1 — all ``time.perf_counter`` values)."""
        # the profiler's twins of the two stamped halves, on this worker
        # thread's line (dtpu.loader.decode, dtpu.loader.assemble): whether
        # the workers were busy or idle while the device waited under
        # dtpu.trainer.wait is read off a capture from these
        dec0 = time.perf_counter()
        with telemetry_spans.annotate("decode"):
            images, labels = self._decode(idxs)
        dec1 = time.perf_counter()
        with telemetry_spans.annotate("assemble"):
            n = len(images)
            images = np.asarray(images)
            # DATA.DEVICE_NORMALIZE ships uint8 (4× fewer H2D bytes; the
            # trainer normalizes in-graph); otherwise float32 as before. A
            # dataset may pin the payload dtype instead (BATCH_DTYPE — the
            # token species ships int32 ids that must NOT be float-cast or
            # in-graph-normalized, data/shards/tokens.py).
            img_dtype = getattr(self.dataset, "BATCH_DTYPE", None) or (
                np.uint8 if images.dtype == np.uint8 else np.float32
            )
            batch = {
                "image": images.astype(img_dtype, copy=False),
                "label": labels.astype(np.int32),
                "mask": np.ones((n,), np.float32),
            }
            if n < self.batch_size:  # pad ragged final eval batch, mask it out
                pad = self.batch_size - n
                batch["image"] = np.concatenate(
                    [batch["image"],
                     np.zeros((pad,) + batch["image"].shape[1:], img_dtype)]
                )
                # label shape is [B] for classification, [B, S] for the LM —
                # pad shape-generically
                batch["label"] = np.concatenate(
                    [batch["label"],
                     np.zeros((pad,) + batch["label"].shape[1:], np.int32)]
                )
                batch["mask"] = np.concatenate(
                    [batch["mask"], np.zeros(pad, np.float32)]
                )
        asm1 = time.perf_counter()
        if telemetry_spans.enabled() and cfg.TELEMETRY.STEP_SPANS:
            # worker-side halves of the batch timeline, per rank (the
            # primary-only kind="timeline" records carry the same stamps
            # for rank 0; these make a rank-3 decode stall visible)
            telemetry_spans.emit_span("decode", dec0, dec1, track="loader", n=n)
            telemetry_spans.emit_span("assemble", dec1, asm1, track="loader", n=n)
        return batch, {"submit": submit, "dec0": dec0, "dec1": dec1,
                       "asm1": asm1}

    def _fetch_sample(self, i: int):
        """One sample with retry-with-backoff; ``None`` marks a
        persistently corrupt sample (logged, skipped — DATA.SKIP_CORRUPT)
        for the caller to substitute."""
        delay = self.retry_backoff
        err = None
        for attempt in range(self.retries + 1):
            try:
                faults.maybe_decode_error(int(i))  # injection hook (tests)
                return self.dataset[int(i)]
            except Exception as e:
                err = e
                if attempt < self.retries:
                    time.sleep(delay)
                    delay *= 2
        if not self.skip_corrupt:
            raise RuntimeError(
                f"sample {int(i)} failed decode after {self.retries + 1} "
                "attempts (DATA.SKIP_CORRUPT False — fail-stop)"
            ) from err
        get_logger().warning(
            "corrupt sample %d skipped after %d attempts (%s: %s) — "
            "substituting a good sample from the same batch",
            int(i), self.retries + 1, type(err).__name__, err,
        )
        metrics_log(
            "data_error", index=int(i), attempts=self.retries + 1,
            error=f"{type(err).__name__}: {err}",
        )
        return None

    def _decode(self, idxs) -> tuple:
        """(images, labels) via the batch kernel when available, else
        per-sample — both behind retry-with-backoff. A batch-level decode
        that keeps failing falls back to the per-sample path, which
        isolates and substitutes the corrupt sample(s) instead of
        aborting the epoch."""
        if hasattr(self.dataset, "load_batch"):
            delay = self.retry_backoff
            err = None
            for attempt in range(self.retries + 1):
                try:
                    for i in idxs:
                        faults.maybe_decode_error(int(i))
                    return self.dataset.load_batch(
                        idxs, n_threads=self.workers
                    )
                except Exception as e:
                    err = e
                    if attempt < self.retries:
                        time.sleep(delay)
                        delay *= 2
            if not self.skip_corrupt:
                raise RuntimeError(
                    f"batch decode failed after {self.retries + 1} attempts "
                    "(DATA.SKIP_CORRUPT False — fail-stop)"
                ) from err
            get_logger().warning(
                "batch decode failed after %d attempts (%s: %s) — "
                "isolating per-sample", self.retries + 1,
                type(err).__name__, err,
            )
        samples = [self._fetch_sample(i) for i in idxs]
        good = [s for s in samples if s is not None]
        if not good:
            raise RuntimeError(
                f"all {len(list(idxs))} samples in the batch failed decode — "
                "not a stray corrupt file; check the dataset/storage "
                "(first indices: " + ", ".join(str(int(i)) for i in list(idxs)[:4]) + ")"
            )
        samples = [s if s is not None else good[0] for s in samples]
        images = np.stack([p[0] for p in samples])
        labels = np.asarray([p[1] for p in samples], np.int32)
        return images, labels

    def last_timing(self) -> dict | None:
        """Stage timestamps (submit/dec0/dec1/asm1) of the most recently
        yielded batch — the loader half of the per-batch timeline
        (single-consumer iteration, so "last yielded" is unambiguous)."""
        return self._last_timing

    def __iter__(self):
        self._last_timing = None
        idxs = self.sampler.indices()
        n_batches = len(self)
        chunks = [
            idxs[b * self.batch_size : (b + 1) * self.batch_size]
            for b in range(n_batches)
        ]
        if self._resume is not None and self._resume["epoch"] == self._epoch:
            # exact mid-epoch resume: the skipped batches were already
            # consumed (and trained) by the preempted run — jump the
            # cursor, don't decode them (one-shot; later epochs are whole)
            chunks = chunks[self._resume["skip"] :]
            self._resume = None
        # Parallel background assembly (the torch worker-pool analogue):
        # `workers` batches decode/augment concurrently ahead of the consumer.
        # PIL decode and numpy transforms release the GIL, so threads give
        # real decode parallelism; batch order is preserved.
        with ThreadPoolExecutor(max_workers=self.prefetch_depth) as pool:
            in_flight: deque = deque()
            chunk_iter = iter(chunks)
            for chunk in chunks[: self.prefetch_depth]:
                in_flight.append(
                    pool.submit(self._assemble, chunk, time.perf_counter())
                )
                next(chunk_iter)
            while in_flight:
                batch, timing = in_flight.popleft().result()
                nxt = next(chunk_iter, None)
                if nxt is not None:
                    in_flight.append(
                        pool.submit(self._assemble, nxt, time.perf_counter())
                    )
                self._last_timing = timing
                yield batch


def device_prefetch(loader, put_fn, depth: int):
    """Device-side prefetch ring over a host-batch iterable.

    Yields ``(it, device_batch, timing)`` in loader order. With
    ``depth > 0`` the ring keeps the NEXT ``depth`` batches already put
    (``put_fn`` = the sharded ``jax.device_put``, an async dispatch), so
    the H2D transfers of batches k+1..k+depth overlap the consumer's step
    on batch k instead of serializing behind it. ``depth 0`` reproduces
    the unoverlapped put-then-step order exactly. Any depth is
    value-bit-identical: the put order, step order, and batch contents
    never change — only when each transfer is dispatched.

    ``timing`` carries the loader's assembly stamps (when the iterable is
    a ``Loader``) plus ``get0/get1`` (consumer blocked on the host batch)
    and ``put0/put1`` (H2D dispatch) — the consumer-side half of the
    utils/jsonlog timeline schema; the caller adds ``step0/step1``.
    """
    get_timing = getattr(loader, "last_timing", lambda: None)
    src = iter(loader)

    def pull():
        # the profiler's twin of each stamped interval (dtpu.trainer.wait,
        # dtpu.trainer.h2d): the JSONL spans are emitted later from the
        # stamps (trainer._emit_batch_spans) and cannot annotate then
        get0 = time.perf_counter()
        with telemetry_spans.annotate("wait"):
            try:
                hb = next(src)
            except StopIteration:
                return None
        get1 = time.perf_counter()
        tl = dict(get_timing() or {})
        tl["get0"], tl["get1"] = get0, get1
        tl["n"] = int(np.shape(hb["image"])[0]) if "image" in hb else 0
        tl["put0"] = time.perf_counter()
        with telemetry_spans.annotate("h2d"):
            db = put_fn(hb)
        tl["put1"] = time.perf_counter()
        return db, tl

    ring: deque = deque()
    exhausted = False
    it = 0
    while True:
        while not exhausted and len(ring) < max(0, depth) + 1:
            item = pull()
            if item is None:
                exhausted = True
            else:
                ring.append(item)
        if not ring:
            return
        db, tl = ring.popleft()
        yield it, db, tl
        it += 1


def _build_dataset(split: str, train: bool):
    raw_u8 = bool(cfg.DATA.DEVICE_NORMALIZE)
    if cfg.MODEL.DUMMY_INPUT:
        # dummy images are model-input-sized for both splits (the reference
        # likewise uses 224² dummies everywhere, utils.py:125,159)
        return DummyDataset(
            length=cfg.TRAIN.BATCH_SIZE * 64, size=cfg.TRAIN.IM_SIZE,
            raw_u8=raw_u8,
        )
    root = cfg.TRAIN.DATASET if train else cfg.TEST.DATASET
    # train: RandomResizedCrop target; val: shorter-side resize to
    # TEST.IM_SIZE, center-crop to the model input size TRAIN.IM_SIZE
    # (ref: utils.py:131,169-170 — Resize(256) + CenterCrop(224))
    im_size = cfg.TRAIN.IM_SIZE if train else cfg.TEST.IM_SIZE
    common = dict(
        im_size=im_size, train=train,
        base_seed=cfg.RNG_SEED or 0,
        crop_size=None if train else cfg.TRAIN.IM_SIZE,
        backend=cfg.DATA.BACKEND,
        raw_u8=raw_u8,
    )
    if cfg.DATA.FORMAT == "tokens":
        # packed-sequence token shards (data/shards/tokens.py, packed by
        # tools/make_token_shards.py) — the LM pipeline. Same container,
        # same window-shuffled order, same exact mid-epoch resume; the
        # image-specific transform knobs don't apply. Pack/seq-len and
        # tokenizer/vocab identity are refused here, before any compile.
        from distribuuuu_tpu.data.shards.tokens import TokenShardDataset

        return TokenShardDataset(
            root, split, seq_len=int(cfg.LM.SEQ_LEN),
            num_classes=int(cfg.MODEL.NUM_CLASSES),
        )
    if cfg.DATA.FORMAT == "shards":
        # indexed record shards (data/shards/) — DATASET points at the
        # packed root (tools/make_shards.py); sequential IO + exact
        # mid-epoch resume
        from distribuuuu_tpu.data.shards.reader import ShardDataset

        return ShardDataset(root, split, **common)
    if cfg.DATA.FORMAT != "imagefolder":
        raise ValueError(
            f"DATA.FORMAT must be imagefolder|shards|tokens, got "
            f"{cfg.DATA.FORMAT!r}"
        )
    from distribuuuu_tpu.data.imagefolder import ImageFolderDataset

    return ImageFolderDataset(root, split, **common)


def construct_train_loader() -> Loader:
    """Train pipeline (ref: utils.py:121-152): shuffled, drop_last."""
    dataset = _build_dataset(cfg.TRAIN.SPLIT, train=True)
    return Loader(
        dataset,
        batch_size=_per_host_batch(cfg.TRAIN.BATCH_SIZE),
        shuffle=True,
        drop_last=True,
        workers=cfg.TRAIN.WORKERS,
        seed=cfg.RNG_SEED or 0,
    )


def construct_val_loader() -> Loader:
    """Val pipeline (ref: utils.py:155-184): unshuffled, keep ragged tail."""
    dataset = _build_dataset(cfg.TEST.SPLIT, train=False)
    return Loader(
        dataset,
        batch_size=_per_host_batch(cfg.TEST.BATCH_SIZE),
        shuffle=False,
        drop_last=False,
        workers=cfg.TRAIN.WORKERS,
        seed=cfg.RNG_SEED or 0,
    )


def _per_host_batch(per_chip_batch: int) -> int:
    """BATCH_SIZE is per-chip (the reference's per-GPU meaning,
    README.md:197); each host feeds all its local chips."""
    n_local = jax.local_device_count()
    return per_chip_batch * n_local
