"""Distributed checkpoint with reshard-on-load + preemption-safe commit.

Reference: `python/paddle/distributed/checkpoint/` — save_state_dict
(per-rank shard files + global Metadata of LocalTensorMetadata offsets),
load_state_dict (:467) computing shard overlaps (compute_overlap:335) and
resharding via collectives.

TPU-native: orbax-style layout-agnostic checkpointing comes for free from
jax.Array: save writes each process's addressable shards + a metadata
index; load places data into whatever NamedSharding the current program
wants (device_put does the reshard).  Single-controller saves/loads the
full array directly.

Fault tolerance (the part a preemptible v5p job actually leans on):

* every shard file is written tmp → fsync → rename (a crash mid-write
  can never leave a half shard at the final name);
* each shard carries a `<shard>.shard.json` sidecar with the whole-file
  CRC + size, verified by `is_complete` before a checkpoint is trusted
  (bit rot / post-rename truncation is detected, not loaded);
* `save_checkpoint(root, step)` lays out `root/step_<N>/` dirs and
  commits `root/latest` (atomically, AFTER every shard landed and
  verified) — readers that follow `latest` never observe a torn step;
* `load_checkpoint` walks latest-then-newest-complete, so a torn or
  corrupt newest step falls back to the previous complete one;
* shard writes retry with bounded exponential backoff on transient IO
  errors (FLAGS_ckpt_write_retries);
* old step dirs are garbage-collected after each successful commit
  (`keep` newest complete steps are retained);
* a failed ASYNC save surfaces at the next `save_state_dict` call
  immediately (fail-fast), not only at `synchronize_async_saves`.

Fault-injection points (`paddle_tpu.distributed.fault`): `ckpt.write`
(modes truncate/corrupt/error per shard), `ckpt.manifest` (skip/error)
and `ckpt.latest` (skip/error) — every recovery branch above has a
planted-fault test driven through them.
"""
from __future__ import annotations

import json
import os
import pickle
import shutil
import threading
import time
import zlib
from concurrent.futures import ThreadPoolExecutor, Future

import numpy as np
import jax
import jax.numpy as jnp

from ...framework.tensor import Tensor
from ...framework.flags import define_flag, get_flag
from .. import fault
from .reshard import (ReshardError, ShardSlice, assemble, index_volume,
                      normalize_index, split_index)

__all__ = ["save_state_dict", "load_state_dict",
           "synchronize_async_saves", "save_checkpoint",
           "load_checkpoint", "latest_checkpoint", "is_complete",
           "checkpoint_meta", "save_train_checkpoint",
           "restore_train_checkpoint", "optimizer_meta",
           "apply_optimizer_meta", "ReshardError", "ShardSlice",
           "cursor_to_meta", "cursor_from_meta"]

define_flag("ckpt_write_retries", 3,
            "attempts per checkpoint shard write before the IO error "
            "propagates (transient-error retry with exponential backoff)")
define_flag("ckpt_retry_backoff", 0.02,
            "base seconds of the checkpoint-write retry backoff "
            "(doubles per attempt)")
define_flag("ckpt_commit_verify_crc", True,
            "re-read and CRC-verify every shard at `latest` commit "
            "(catches write-path bit-rot before the pointer moves); "
            "disable on multi-GB states to avoid a full-checkpoint "
            "read per save — size/manifest checks still run, and "
            "post-crash load always verifies CRCs")
define_flag("ckpt_save_sharded", False,
            "write sharded arrays as per-shard slices with global index "
            "metadata even when fully addressable (single-controller "
            "SPMD) — the elastic reshard-on-load contract: the on-disk "
            "layout matches what a multi-host save of the same mesh "
            "would produce, and any other topology reassembles it from "
            "the overlapping slices.  Off (default) keeps the r9 "
            "gathered-full-array format byte-identical")


def _proc_rank_world():
    """(rank, world) identity of the saving/loading PROCESS.  A real
    multi-host jax runtime answers jax.process_index/count; an N-proc
    host-plane fleet job (one single-device jax per rank, rendezvoused
    by the launch controller) answers PADDLE_TRAINER_ID/NUM — so each
    fleet rank writes its own `<rank>.distcp` and the coordinator-only
    commit/GC contract holds across both planes."""
    pc = jax.process_count()
    if pc > 1:
        return jax.process_index(), pc
    from ..host_collectives import host_world
    r, w = host_world()
    return (r, w) if w > 1 else (0, 1)

# single-worker writer: async saves queue here (reference
# save_state_dict.py:46 — a dedicated save process fed from a queue);
# device->host snapshots happen synchronously (the step may donate the
# buffers), only the file IO is deferred
_writer: ThreadPoolExecutor = None
_pending: list = []
_pending_lock = threading.Lock()
# first unobserved async-writer error: re-raised by the NEXT
# save_state_dict (fail-fast) or by synchronize_async_saves, whichever
# comes first (then cleared)
_writer_error: list = []

# write-activity counter: tests/test_fault_tolerance.py `TestZeroOverhead`
# holds the flags-off train hot path to zero checkpoint IO
WRITE_CALLS = 0


def _get_writer():
    global _writer
    if _writer is None:
        _writer = ThreadPoolExecutor(max_workers=1,
                                     thread_name_prefix="ckpt-writer")
    return _writer


def _store_writer_error(exc: BaseException):
    with _pending_lock:
        if not _writer_error:
            _writer_error.append(exc)


def _prune_pending_locked():
    """Drop settled futures (caller holds _pending_lock).  Safe: every
    failure is also captured in _writer_error by the job wrappers, so
    synchronize_async_saves still surfaces it — this just keeps
    _pending bounded by the writer-queue depth instead of growing one
    entry per save over a long run."""
    _pending[:] = [f for f in _pending if not f.done()]


def _take_writer_error():
    with _pending_lock:
        return _writer_error.pop() if _writer_error else None


def synchronize_async_saves():
    """Step-boundary barrier: block until every queued async save hit
    disk, re-raising the first writer error (reference: the sync point
    before the next save / at exit)."""
    with _pending_lock:
        futs, _pending[:] = list(_pending), []
    first = None
    for f in futs:
        try:
            f.result()
        except BaseException as e:     # noqa: BLE001 — re-raised below
            first = first or e
    stored = _take_writer_error()
    if first is not None:
        raise first
    if stored is not None:
        raise stored


_MAGIC = b"PDCP2\x00"


def _fsync_path(fd_path):
    """fsync a directory so a rename into it survives power loss
    (best-effort: not all platforms allow O_RDONLY dir fds)."""
    try:
        fd = os.open(fd_path, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)
    except OSError:
        pass


def _atomic_write_bytes(fname, data: bytes):
    """tmp + fsync + rename for small control files (manifest, latest,
    sidecars)."""
    tmp = fname + f".tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, fname)
    _fsync_path(os.path.dirname(fname) or ".")


def _with_retry(fn, what: str):
    """Bounded retry with exponential backoff for transient IO errors
    (reference: large-job save paths retry NFS/GCS blips rather than
    failing the step).  Non-IO errors propagate immediately."""
    attempts = max(1, int(get_flag("ckpt_write_retries") or 1))
    backoff = float(get_flag("ckpt_retry_backoff") or 0.02)
    for i in range(attempts):
        try:
            return fn()
        except (IOError, OSError) as e:
            if i == attempts - 1:
                raise
            import warnings
            warnings.warn(
                f"checkpoint: transient failure in {what} (attempt "
                f"{i + 1}/{attempts}): {e}; retrying", RuntimeWarning)
            time.sleep(backoff * (2 ** i))


def _write_files(path, rank, shards, meta, coordinator_rank):
    """Container v2: json header (shard index: dtype/shape/offset/crc)
    + one contiguous payload region.  The payload goes through the
    native multithreaded writer (csrc/io_native.cc) when the toolchain
    built it — the native analog of the reference's compiled save path
    — else a plain Python write.  Legacy pickle files remain loadable.

    Hardened: the shard is written to a tmp name, fsynced and renamed;
    the whole-file CRC lands in a `.shard.json` sidecar AFTER the
    rename, so a reader that finds the sidecar knows the shard bytes
    are the ones the writer intended."""
    global WRITE_CALLS
    WRITE_CALLS += 1
    header = {"version": 2, "entries": []}
    blobs = []
    off = 0

    def add(arr):
        # keep the contiguous ARRAY, not a tobytes() copy — holding raw
        # bytes for every tensor doubles peak host memory on multi-GB
        # states; crc and the write both go through the buffer protocol
        nonlocal off
        arr = np.ascontiguousarray(arr)
        # uint8 view (not a copy): ml_dtypes arrays (bfloat16/fp8)
        # refuse PEP-3118 memoryview export, so downstream buffer
        # consumers need a native-dtype view of the same bytes
        u8 = arr.reshape(-1).view(np.uint8)
        ent = {"offset": off, "nbytes": arr.nbytes,
               "dtype": str(arr.dtype), "shape": list(arr.shape),
               "crc": zlib.crc32(u8) & 0xFFFFFFFF}
        blobs.append(u8)
        off += arr.nbytes
        return ent

    for k, v in shards.items():
        if isinstance(v, dict) and "local" in v:
            locs = []
            for arr, idx in zip(v["local"], v["index"]):
                e = add(arr)
                e["index"] = [list(p) for p in idx]
                locs.append(e)
            header["entries"].append({"key": k, "sharded": True,
                                      "locals": locs})
        else:
            e = add(v)
            e["key"] = k
            header["entries"].append(e)

    hdr = json.dumps(header).encode()
    prefix = _MAGIC + len(hdr).to_bytes(8, "little") + hdr
    # whole-file CRC (prefix + every blob, in order) for the sidecar
    file_crc = zlib.crc32(prefix)
    for arr in blobs:
        file_crc = zlib.crc32(arr, file_crc)
    file_crc &= 0xFFFFFFFF
    nbytes = len(prefix) + off
    fname = os.path.join(path, f"{rank}.distcp")
    tmp = fname + f".tmp.{os.getpid()}"

    def _write_payload(out):
        from ... import _native
        io = _native.io_lib()
        if io is not None and blobs:
            # coalesce small blobs into a bounded (64 MiB) buffer so the
            # small-parameter tail costs O(1) native write calls, while
            # multi-GB tensors still stream without a full-payload join
            io.write(out, prefix, 0, 1)
            pos = len(prefix)
            buf, buf_pos, buf_size = [], pos, 0
            FLUSH = 64 * 1024 * 1024

            def flush():
                nonlocal buf, buf_size
                if buf:
                    io.write(out, b"".join(buf), buf_pos, 8)
                    buf, buf_size = [], 0

            for arr in blobs:
                if arr.nbytes >= FLUSH:
                    flush()
                    io.write(out, arr, pos, 8)  # zero-copy buffer write
                else:
                    if not buf:
                        buf_pos = pos
                    buf.append(arr)   # b"".join accepts uint8 views
                    buf_size += arr.nbytes
                    if buf_size >= FLUSH:
                        flush()
                pos += arr.nbytes
            flush()
            # durability before the rename publishes the file
            fd = os.open(out, os.O_RDWR)
            try:
                os.fsync(fd)
            finally:
                os.close(fd)
        else:
            with open(out, "wb") as f:
                f.write(prefix)
                for arr in blobs:
                    f.write(arr)      # uint8 views: buffer write, no copy
                f.flush()
                os.fsync(f.fileno())

    injected = []

    def _attempt():
        injected[:] = [fault.hit("ckpt.write", key=fname)]  # error raises
        _write_payload(tmp)
        os.replace(tmp, fname)
        _fsync_path(path)

    _with_retry(_attempt, f"write {fname}")

    # planted at-rest defects (torn / bit-rot) applied AFTER the atomic
    # rename: the dangerous case is a save that LOOKS successful —
    # is_complete must catch it on load
    inj = injected[0] if injected else None
    if inj is not None and inj.mode == "truncate":
        with open(fname, "r+b") as fh:
            fh.truncate(max(1, nbytes // 2))
    elif inj is not None and inj.mode == "corrupt":
        with open(fname, "r+b") as fh:
            fh.seek(max(0, nbytes - 1))
            b = fh.read(1)
            fh.seek(max(0, nbytes - 1))
            fh.write(bytes([b[0] ^ 0xFF]) if b else b"\xff")

    _atomic_write_bytes(
        fname + ".shard.json",
        json.dumps({"crc": file_crc, "nbytes": nbytes,
                    "rank": rank}).encode())
    if rank == coordinator_rank:
        mf = fault.hit("ckpt.manifest", key=path)
        if mf is None or mf.mode not in ("skip",):
            _atomic_write_bytes(os.path.join(path, "metadata.json"),
                                json.dumps(meta).encode())


def _entry_reader(fpath):
    """Parse one .distcp file's HEADER (v2 container; legacy pickle
    reads the whole dict) into ``(pieces, close)`` where pieces is

        [(key, index_or_None, shape, fetch)]

    ``index`` is a normalized global slice tuple for sharded entries
    (None = a full-tensor entry) and ``fetch()`` lazily reads and
    CRC-verifies just that entry's payload — reshard-on-load only
    touches the bytes of the slices that actually overlap the target.
    All fetchers share ONE read-only fd (seek-free ``os.pread``; large
    entries ride the parallel native reader instead); the caller closes
    it via ``close()`` once assembly is done."""
    with open(fpath, "rb") as f:
        head = f.read(len(_MAGIC))
        if head != _MAGIC:
            f.seek(0)
            legacy = pickle.load(f)
            out = []
            for k, v in legacy.items():
                if isinstance(v, dict) and "local" in v:
                    # global extent per dim: an index pair with stop
                    # None means "the full dim" — resolve it from the
                    # piece's own local extent, not a zero default
                    ndim = np.asarray(v["local"][0]).ndim
                    dims = [0] * ndim
                    for local, index in zip(v["local"], v["index"]):
                        for d, p in enumerate(index):
                            start = (p.start if isinstance(p, slice)
                                     else p[0]) or 0
                            stop = p.stop if isinstance(p, slice) \
                                else p[1]
                            if stop is None:
                                stop = start + int(
                                    np.asarray(local).shape[d])
                            dims[d] = max(dims[d], int(stop))
                    for local, index in zip(v["local"], v["index"]):
                        idx = normalize_index(
                            [p if isinstance(p, slice)
                             else slice(p[0] or 0, p[1]) for p in index],
                            dims)
                        out.append((k, idx, local.shape,
                                    (lambda a=local: a)))
                else:
                    out.append((k, None, np.asarray(v).shape,
                                (lambda a=v: np.asarray(a))))
            return out, (lambda: None)
        hlen = int.from_bytes(f.read(8), "little")
        header = json.loads(f.read(hlen))
        base = len(_MAGIC) + 8 + hlen

    state = {"fd": None}
    _NATIVE_MIN = 8 * 1024 * 1024

    def _pread(off, nbytes):
        if nbytes >= _NATIVE_MIN:
            from ... import _native
            io = _native.io_lib()
            if io is not None:
                return io.read(fpath, nbytes, off, 8)
        if state["fd"] is None:
            state["fd"] = os.open(fpath, os.O_RDONLY)
        chunks, want = [], nbytes
        while want > 0:
            b = os.pread(state["fd"], want, off)
            if not b:
                break   # short file: the length/CRC check reports it
            chunks.append(b)
            off += len(b)
            want -= len(b)
        return chunks[0] if len(chunks) == 1 else b"".join(chunks)

    def close():
        if state["fd"] is not None:
            os.close(state["fd"])
            state["fd"] = None

    def mat(e):
        raw = _pread(base + e["offset"], e["nbytes"])
        if len(raw) != e["nbytes"] \
                or (zlib.crc32(raw) & 0xFFFFFFFF) != e["crc"]:
            raise IOError(
                f"checkpoint corruption in {fpath}: crc mismatch")
        return np.frombuffer(raw, np.dtype(e["dtype"])) \
            .reshape(e["shape"]).copy()

    out = []
    for ent in header["entries"]:
        if ent.get("sharded"):
            for e in ent["locals"]:
                # pre-reshard v2 files serialized a replicated dim's
                # slice as [start, null] (stop None = the full dim) —
                # resolve it from the blob's own local extent
                idx = tuple(
                    (int(p[0] or 0),
                     int(p[1]) if p[1] is not None
                     else int(p[0] or 0) + int(s))
                    for p, s in zip(e["index"], e["shape"]))
                out.append((ent["key"], idx, tuple(e["shape"]),
                            (lambda e=e: mat(e))))
        else:
            out.append((ent["key"], None, tuple(ent["shape"]),
                        (lambda e=ent: mat(e))))
    return out, close


def _legacy_gshape(indices, local=None):
    """Best-effort global shape for sharded entries without manifest
    metadata (the max stop per dim across the known slices)."""
    ndim = np.asarray(local).ndim if local is not None \
        else max((len(ix) for ix in indices), default=0)
    dims = [0] * ndim
    for ix in indices:
        for d, p in enumerate(ix):
            stop = p.stop if isinstance(p, slice) else p[1]
            dims[d] = max(dims[d], int(stop or 0))
    return dims


def _unique_shards(arr):
    """[(normalized_index, np_data)] of an addressable jax array's
    DISTINCT shards — replicated copies (dp axes) dedupe to one slice
    per index, so a dp=8 replicated param still writes one full copy
    and a dp=2×sharding=4 moment writes 4 slices, not 8."""
    out, seen = [], set()
    for s in arr.addressable_shards:
        idx = normalize_index(s.index, arr.shape)
        if idx in seen:
            continue
        seen.add(idx)
        out.append((idx, np.asarray(s.data)))
    return out


def save_state_dict(state_dict, path, process_group=None,
                    coordinator_rank=0, async_save=False, meta_extra=None,
                    rank=None, world=None, save_sharded=None):
    """async_save=True: snapshot to host now, write files on the
    background queue; returns a Future (also joined by
    synchronize_async_saves).  A previously failed async save raises
    HERE, immediately (fail-fast), instead of waiting for the next
    synchronize_async_saves.

    Reshard-on-load contract: values may be :class:`ShardSlice` objects
    (this rank's slice of a globally-shaped tensor — the host-plane
    fleet path), and with ``save_sharded`` (default:
    FLAGS_ckpt_save_sharded) mesh-sharded jax arrays are written as
    per-shard slices with global index metadata instead of a gathered
    full array.  The manifest records each tensor's global shape, dtype
    and — for sharded saves — the writing rank's shard-slice layout, so
    any later topology reassembles its own shards from the overlaps.
    `rank`/`world` override the process identity (tooling/tests);
    defaults follow jax.process_index/count or, for host-plane fleet
    jobs, PADDLE_TRAINER_ID/NUM."""
    stored = _take_writer_error()
    if stored is not None:
        # raising here OBSERVES the failure: drop the already-settled
        # futures so the next synchronize_async_saves doesn't re-raise
        # the same error a second time
        with _pending_lock:
            _prune_pending_locked()
        raise stored
    os.makedirs(path, exist_ok=True)
    prank, pworld = _proc_rank_world()
    rank = prank if rank is None else int(rank)
    world = pworld if world is None else int(world)
    if save_sharded is None:
        save_sharded = bool(get_flag("ckpt_save_sharded"))
    meta = {}
    shards = {}
    for k, v in state_dict.items():
        if isinstance(v, ShardSlice):
            idx = v.index
            shards[k] = {"local": [v.data], "index": [list(idx)]}
            meta[k] = {"global_shape": list(v.global_shape),
                       "dtype": str(v.data.dtype), "rank": rank,
                       "sharded": True,
                       "layout": [[list(p) for p in idx]]}
            continue
        arr = v.value if isinstance(v, Tensor) else jnp.asarray(v)
        fully = getattr(arr, "is_fully_addressable", True)
        sharding = getattr(arr, "sharding", None)
        # a mesh-sharded array under the reshard contract writes real
        # slices; a replicated one still gathers to one full copy
        split = (not fully) or (
            save_sharded and sharding is not None
            and not getattr(sharding, "is_fully_replicated", True)
            and getattr(arr, "ndim", 0) >= 1)
        if not split:
            np_arr = np.asarray(arr)
            shards[k] = np_arr
            meta[k] = {"global_shape": list(np_arr.shape),
                       "dtype": str(np_arr.dtype),
                       "rank": rank}
        else:
            uniq = _unique_shards(arr)
            shards[k] = {"local": [d for _, d in uniq],
                         "index": [list(ix) for ix, _ in uniq]}
            meta[k] = {"global_shape": list(arr.shape),
                       "dtype": str(arr.dtype), "rank": rank,
                       "sharded": True,
                       "layout": [[list(p) for p in ix]
                                  for ix, _ in uniq]}
    # completeness contract: the manifest records how many rank shards
    # this checkpoint must contain (and any train-loop metadata)
    meta["__world__"] = world
    if meta_extra is not None:
        meta["__train_meta__"] = meta_extra
    if async_save:
        def job():
            try:
                _write_files(path, rank, shards, meta, coordinator_rank)
            except BaseException as e:   # noqa: BLE001 — stored for
                _store_writer_error(e)   # fail-fast at the next save
                raise
        fut = _get_writer().submit(job)
        with _pending_lock:
            _prune_pending_locked()
            _pending.append(fut)
        return fut
    _write_files(path, rank, shards, meta, coordinator_rank)
    done = Future()
    done.set_result(None)
    return done


def load_state_dict(state_dict, path, process_group=None,
                    coordinator_rank=0, offload=False, coverage=None):
    """In-place load into `state_dict` values, resharding each tensor
    to its CURRENT target layout — this is reshard-on-load, the default
    checkpoint contract:

    * a Tensor target is assembled from the overlapping saved slices of
      whatever topology produced the checkpoint (full arrays, stage-3
      'sharding' splits, per-rank fleet slices) and placed into its own
      NamedSharding — sharded targets assemble per LOCAL shard via
      jax.make_array_from_callback, so the full array never
      materializes host-side;
    * a :class:`ShardSlice` target (host-plane fleet rank) gets exactly
      its slice of the new world filled into ``.data``.

    A topology the save cannot satisfy — global-shape mismatch, or a
    coverage gap from missing rank shard files — raises the named
    :class:`ReshardError` instead of an opaque shard-count error.
    `coverage` (optional dict) is filled with `missing` (state_dict
    keys the files didn't provide) and `unexpected` (file keys
    state_dict didn't ask for) so callers that require a FULL restore
    can fail or warn loudly."""
    files = [f for f in os.listdir(path) if f.endswith(".distcp")]
    meta = None
    try:
        with open(os.path.join(path, "metadata.json")) as mf:
            meta = json.load(mf)
    except (OSError, ValueError):
        pass
    detail = ""
    if meta is not None and "__world__" in meta:
        # read exactly the ranks this save produced: a re-save into the
        # same step dir after an elastic world SHRINK leaves stale
        # higher-rank shards behind, and mixing them in would silently
        # restore old-step values
        world = int(meta["__world__"])
        expected = {f"{r}.distcp" for r in range(world)}
        missing = sorted(expected - set(files))
        files = [f for f in files if f in expected]
        if missing:
            detail = (f"; saved at world {world} but rank file(s) "
                      f"{missing} are absent")
    # piece index: key -> [(normalized_index|None, shape, fetch)]
    pieces = {}
    closers = []
    try:
        for fname in sorted(files):
            plist, close = _entry_reader(os.path.join(path, fname))
            closers.append(close)
            for k, idx, shape, fetch in plist:
                pieces.setdefault(k, []).append((idx, shape, fetch))
        return _assemble_targets(state_dict, pieces, meta, detail,
                                 coverage)
    finally:
        for close in closers:
            close()


def _assemble_targets(state_dict, pieces, meta, detail, coverage):
    """Reshard-on-load assembly: fill every ``state_dict`` target from
    the overlapping saved pieces (the back half of load_state_dict —
    split out so the caller can close the shared per-file fds the
    fetchers read through as soon as assembly finishes)."""
    if coverage is not None:
        coverage["missing"] = sorted(set(state_dict) - set(pieces))
        coverage["unexpected"] = sorted(set(pieces) - set(state_dict))
    for k, t in state_dict.items():
        plist = pieces.get(k)
        if not plist:
            continue
        kmeta = (meta or {}).get(k) or {}
        gshape = kmeta.get("global_shape")
        if gshape is None:
            full = next((shape for idx, shape, _ in plist
                         if idx is None), None)
            gshape = list(full) if full is not None \
                else _legacy_gshape([idx for idx, _, _ in plist
                                     if idx is not None], None)
        gshape = tuple(int(d) for d in gshape)
        sdtype = np.dtype(kmeta["dtype"]) if kmeta.get("dtype") \
            else None
        def _memo(fn):
            # each saved piece is read from disk AT MOST once per key,
            # however many local target shards its slice overlaps
            box = []

            def get():
                if not box:
                    box.append(fn())
                return box[0]
            return get

        norm = [(normalize_index(idx, gshape) if idx is not None
                 else normalize_index(None, gshape), _memo(fetch))
                for idx, _, fetch in plist]
        if isinstance(t, ShardSlice):
            if gshape != t.global_shape:
                raise ReshardError(
                    f"checkpoint key {k!r}: saved global shape "
                    f"{gshape} != ShardSlice global shape "
                    f"{t.global_shape}{detail}")
            if t.data is None:
                t.data = np.zeros(t.local_shape,
                                  sdtype or np.float32)
            assemble(t.index, norm, t.data, key=k, detail=detail)
            continue
        tgt = t.value
        tshape = tuple(getattr(tgt, "shape", gshape))
        if gshape != tshape:
            raise ReshardError(
                f"checkpoint key {k!r}: saved global shape {gshape} "
                f"!= target shape {tshape}{detail} — an elastic resume "
                "must keep global shapes; reshard by giving the target "
                "its new mesh sharding (or a ShardSlice), not a "
                "different shape")
        sharding = getattr(tgt, "sharding", None)
        if sdtype is None:
            probe = norm[0][1]()
            sdtype = probe.dtype
            norm[0] = (norm[0][0], (lambda a=probe: a))
        whole = next((f for idx, f in norm
                      if index_volume(idx) == index_volume(
                          normalize_index(None, gshape))), None)
        from jax.sharding import NamedSharding
        if whole is not None:
            arr = jnp.asarray(whole())
            if sharding is not None:
                arr = jax.device_put(arr.astype(tgt.dtype), sharding)
        elif isinstance(sharding, NamedSharding) \
                and getattr(sharding, "num_devices",
                            len(sharding.device_set)) > 1:
            # assemble each LOCAL shard of the target sharding from the
            # overlapping saved slices — the full array never exists
            tdt = np.dtype(tgt.dtype)

            def cb(idx, _k=k, _g=gshape, _n=norm, _dt=sdtype, _t=tdt):
                tix = normalize_index(idx, _g)
                out = np.zeros(tuple(e - s for s, e in tix), _dt)
                assemble(tix, _n, out, key=_k, detail=detail)
                return out.astype(_t, copy=False)

            arr = jax.make_array_from_callback(gshape, sharding, cb)
        else:
            out = np.zeros(gshape, sdtype)
            assemble(normalize_index(None, gshape), norm, out,
                     key=k, detail=detail)
            arr = jnp.asarray(out)
            if sharding is not None:
                arr = jax.device_put(arr.astype(tgt.dtype), sharding)
        t._value = arr
    return state_dict


# ---------------------------------------------------------------------------
# step-dir layout: root/step_<N>/ shards + manifest, root/latest pointer
# ---------------------------------------------------------------------------

_STEP_PREFIX = "step_"


def _step_dirname(step: int) -> str:
    return f"{_STEP_PREFIX}{int(step):08d}"


def _step_of(dirname: str):
    if not dirname.startswith(_STEP_PREFIX):
        return None
    try:
        return int(dirname[len(_STEP_PREFIX):])
    except ValueError:
        return None


def is_complete(path, crc=True) -> bool:
    """True iff `path` holds a committed, verifiable checkpoint: the
    manifest exists, every expected rank shard is present, and each
    shard's bytes match its sidecar CRC + size (the full-file read here
    is the price of trusting a checkpoint after a crash — load_checkpoint
    only pays it for candidate dirs).  ``crc=False`` skips the byte scan
    and trusts manifest + sidecar sizes — the cheap form for retention
    decisions over dirs a commit already fully verified once."""
    try:
        with open(os.path.join(path, "metadata.json")) as f:
            meta = json.load(f)
    except (OSError, ValueError):
        return False
    shards = [f for f in os.listdir(path) if f.endswith(".distcp")]
    world = int(meta.get("__world__", max(1, len(shards))))
    if "__world__" in meta:
        # verify exactly the ranks this save produced — stale leftover
        # shards from a wider pre-resize incarnation don't count (and
        # their bit-rot can't fail an otherwise-healthy checkpoint)
        shards = [s for s in shards
                  if s in {f"{r}.distcp" for r in range(world)}]
    if len(shards) < world:
        return False
    for s in shards:
        fpath = os.path.join(path, s)
        try:
            with open(fpath + ".shard.json") as f:
                side = json.load(f)
            if os.path.getsize(fpath) != int(side["nbytes"]):
                return False
            if not crc:
                continue
            c = 0
            with open(fpath, "rb") as f:
                while True:
                    chunk = f.read(16 * 1024 * 1024)
                    if not chunk:
                        break
                    c = zlib.crc32(chunk, c)
            if (c & 0xFFFFFFFF) != int(side["crc"]):
                return False
        except (OSError, ValueError, KeyError):
            return False
    return True


def checkpoint_meta(path):
    """The `meta_extra` dict stored with a step dir (None if absent)."""
    try:
        with open(os.path.join(path, "metadata.json")) as f:
            return json.load(f).get("__train_meta__")
    except (OSError, ValueError):
        return None


def latest_checkpoint(root):
    """Path of the newest COMPLETE step dir under `root` — or None.

    The scan walks step dirs newest-first and trusts nothing the
    sidecar CRCs don't verify: a torn newest step falls back to the
    previous complete one, and a step whose shards all landed but whose
    `latest` commit was preempted (the emergency-drain crash window) is
    still found and preferred over the stale pointer.  The `latest`
    pointer is the cheap path for external tooling; recovery always
    re-verifies."""
    return _next_candidate(root, ())


def _gc_old_steps(root, keep: int, current: str):
    """Drop all step dirs except the `keep` newest complete ones (the
    just-committed dir always survives).  Incomplete dirs OLDER than the
    current commit are torn leftovers and reaped too."""
    steps = sorted(
        ((s, d) for d in os.listdir(root)
         if (s := _step_of(d)) is not None), reverse=True)
    cur_step = _step_of(current) or 0
    kept = 0
    removed = []
    for s, d in steps:
        p = os.path.join(root, d)
        if d == current:
            kept += 1
            continue
        # cheap completeness (no CRC re-read): every retained dir was
        # fully verified by its own commit; retention only needs to
        # distinguish "landed" from "torn"
        complete = is_complete(p, crc=False)
        if complete and kept < keep:
            kept += 1
        elif complete or s < cur_step:
            # beyond the retention window, or a torn leftover older
            # than this commit; incomplete dirs NEWER than the commit
            # (another writer in flight) are left alone
            shutil.rmtree(p, ignore_errors=True)
            removed.append(d)
    if removed:
        from ... import telemetry as _tel
        _tel.counter("ckpt.gc_removed").inc(len(removed))
        _tel.emit("ckpt.gc", root=root, removed=removed, kept=kept)


def _commit_latest(root, dirname, keep, wait_secs=60.0):
    """Verify the step dir, then atomically publish it as `latest` and
    GC old steps.  An injected crash here (ckpt.latest:mode=skip) leaves
    a complete-but-unpointed dir — which latest_checkpoint's scan still
    finds, and a torn dir is simply never pointed to.

    Only the coordinator rank calls this (single committer); with
    multiple processes it first waits — bounded by `wait_secs`, polling
    the cheap no-CRC completeness — for the other ranks' shards to land
    on the shared filesystem before the full verification."""
    path = os.path.join(root, dirname)
    if _proc_rank_world()[1] > 1:
        deadline = time.monotonic() + wait_secs
        while not is_complete(path, crc=False) \
                and time.monotonic() < deadline:
            time.sleep(0.2)
    verify_crc = bool(get_flag("ckpt_commit_verify_crc"))
    if not is_complete(path, crc=verify_crc):
        raise IOError(
            f"checkpoint {path} failed post-write verification "
            "(torn or corrupt shard) — not committing `latest`")
    f = fault.hit("ckpt.latest", key=path)
    if f is not None and f.mode == "skip":
        return path
    _atomic_write_bytes(os.path.join(root, "latest"), dirname.encode())
    from ... import telemetry as _tel
    _tel.counter("ckpt.commits").inc()
    _tel.emit("ckpt.commit", dir=dirname, root=root,
              step=_step_of(dirname))
    if keep is not None and keep > 0:
        _gc_old_steps(root, keep, dirname)
    return path


def save_checkpoint(state_dict, root, step, keep=3, async_save=False,
                    meta=None, process_group=None, coordinator_rank=0):
    """Write `root/step_<step>/` and commit `root/latest` only after
    every shard landed and verified.  `meta` (JSON-able dict: RNG state,
    data cursor, ...) rides in the manifest.  Returns the step-dir path
    (sync) or a Future of it (async — same single writer thread as
    save_state_dict, so saves land in submission order).  A sync save
    issued while async saves are still queued (the SIGTERM emergency-
    drain path) also rides the writer queue — and blocks on its own
    commit — so an in-flight older step finishes writing before this
    commit's GC could mistake it for a torn leftover.  Only the
    coordinator rank verifies/commits `latest` and runs GC (single
    committer: no cross-rank race on the pointer or rmtree)."""
    dirname = _step_dirname(step)
    path = os.path.join(root, dirname)
    os.makedirs(path, exist_ok=True)
    with _pending_lock:
        _prune_pending_locked()
        queued_behind = bool(_pending)
    on_queue = async_save or queued_behind
    rank, world = _proc_rank_world()
    fut = save_state_dict(state_dict, path, process_group,
                          coordinator_rank, async_save=on_queue,
                          meta_extra=dict(meta or {}, step=int(step),
                                          world=world))
    commit_rank = rank == coordinator_rank
    if not on_queue:
        return _commit_latest(root, dirname, keep) if commit_rank \
            else path

    def chained():
        try:
            fut.result()
        except BaseException:            # noqa: BLE001 — the write job
            # already stored its error for fail-fast; the commit is
            # moot, and re-raising the same exception here would
            # surface it a second time at synchronize_async_saves
            return None
        if not commit_rank:
            return path
        try:
            return _commit_latest(root, dirname, keep)
        except BaseException as e:       # noqa: BLE001
            _store_writer_error(e)
            raise
    # chain on the same writer thread: the commit runs after the shard
    # write job, preserving write→verify→publish order
    cfut = _get_writer().submit(chained)
    if async_save:
        with _pending_lock:
            _prune_pending_locked()
            _pending.append(cfut)
        return cfut
    # sync-behind-async: block here, surfacing a failure exactly once —
    # on error, also drop the settled futures (our failed write fut is
    # in _pending) so synchronize_async_saves doesn't re-raise it
    try:
        out = cfut.result()
    except BaseException as e:           # noqa: BLE001 — observed NOW
        stored = _take_writer_error()
        if stored is not None and stored is not e:
            _store_writer_error(stored)  # unrelated earlier failure
        with _pending_lock:
            _prune_pending_locked()
        raise
    if out is None:                      # our own write job failed
        with _pending_lock:
            _prune_pending_locked()
        raise _take_writer_error() or IOError(
            f"checkpoint write for {path} failed")
    return out


def load_checkpoint(state_dict, root, candidate=None, coverage=None):
    """Restore `state_dict` (in place) from the newest complete step
    under `root`, falling back past torn/corrupt steps.  Returns
    (step, meta) or None when no loadable checkpoint exists.
    `candidate`: a step dir the caller already verified (the restore
    peek) — tried first without paying the CRC scan a second time.
    `coverage`: passed through to load_state_dict."""
    tried = set()
    reshard_err, other_fail = None, False
    while True:
        if candidate is not None:
            path, candidate = candidate, None
        else:
            path = _next_candidate(root, tried)
        if path is None:
            if reshard_err is not None and not other_fail:
                # every candidate failed the RESHARD contract (shape
                # mismatch / coverage gap) rather than corruption:
                # surface the newest named diagnosis instead of a
                # silent cold-start None
                raise reshard_err
            return None
        try:
            load_state_dict(state_dict, path, coverage=coverage)
            meta = checkpoint_meta(path) or {}
            step = meta.get("step", _step_of(os.path.basename(path)))
            return int(step), meta
        except ReshardError as e:
            # a coverage gap in the newest step (e.g. a torn elastic
            # save left stale rank files) falls back like corruption —
            # an older intact step may still satisfy the target
            if reshard_err is None:
                reshard_err = e
            tried.add(path)
        except (IOError, OSError, ValueError, KeyError):
            # completeness said yes but the load failed (e.g. per-entry
            # crc) — fall back to the next newest complete dir
            other_fail = True
            tried.add(path)


def _next_candidate(root, tried):
    """Newest complete step dir under `root` not in `tried` (the one
    shared scan behind latest_checkpoint and load_checkpoint)."""
    if not os.path.isdir(root):
        return None
    steps = sorted(
        ((s, d) for d in os.listdir(root)
         if (s := _step_of(d)) is not None), reverse=True)
    for _, d in steps:
        p = os.path.join(root, d)
        if p not in tried and is_complete(p):
            return p
    return None


# ---------------------------------------------------------------------------
# full-train-state capture/restore for trainer objects
# ---------------------------------------------------------------------------

def optimizer_meta(optimizer) -> dict:
    """The JSON-able non-array half of a TrainState: global step, LR
    scheduler state, and the process RNG (seed, counter) — everything a
    bit-exact resume needs beyond the param/opt arrays."""
    from ...framework import random as prandom
    sched = getattr(optimizer, "_learning_rate_scheduler", None)
    return {
        "step_count": int(optimizer._step_count),
        "lr_sched": dict(sched.state_dict()) if sched is not None
        else None,
        "rng": [list(map(int, s)) for s in prandom.get_rng_state()],
    }


def apply_optimizer_meta(optimizer, meta: dict):
    from ...framework import random as prandom
    optimizer._step_count = int(meta.get("step_count", 0))
    sched = getattr(optimizer, "_learning_rate_scheduler", None)
    if sched is not None and meta.get("lr_sched") is not None:
        sched.set_state_dict(dict(meta["lr_sched"]))
    if meta.get("rng") is not None:
        prandom.set_rng_state([tuple(s) for s in meta["rng"]])


def save_train_checkpoint(trainer, root, step=None, keep=3,
                          async_save=False, extra_meta=None):
    """Capture a trainer's full `TrainState` (params, optimizer state,
    LR scheduler, global step, RNG) via its `train_state()` and write a
    committed step dir.  `trainer` is anything exposing
    `train_state() -> (arrays, meta)` — ShardedTrainStep,
    OffloadPipelineStep, jit.TrainStep, hapi.Model."""
    arrays, meta = trainer.train_state()
    if extra_meta:
        meta = dict(meta, **extra_meta)
    if step is None:
        step = int(meta.get("step_count", 0))
    return save_checkpoint(arrays, root, step, keep=keep,
                           async_save=async_save, meta=meta)


def restore_train_checkpoint(trainer, root):
    """Restore a trainer from the newest complete checkpoint under
    `root`.  Returns the stored meta dict, or None when no checkpoint
    exists (fresh start).  The restore is bit-exact: N steps of
    training ≡ N/2 steps + save + restore-into-fresh-state + N/2."""
    peek = latest_checkpoint(root)
    if peek is None:
        return None
    # trainers with more than one capture format (hapi.Model: jitted
    # TrainStep state vs eager optimizer accumulators) shape their
    # skeleton to the stored checkpoint before we read it — a skeleton
    # from the wrong format would drop the opt-state keys
    prepare = getattr(trainer, "prepare_restore", None)
    if prepare is not None:
        prepare(checkpoint_meta(peek) or {})
    arrays, _ = trainer.train_state()
    # wrap raw arrays so load_state_dict can assign in place
    wrapped = {k: v if isinstance(v, Tensor) else Tensor(v)
               for k, v in arrays.items()}
    cov = {}
    got = load_checkpoint(wrapped, root, candidate=peek, coverage=cov)
    if got is None:
        return None
    if cov.get("missing") or cov.get("unexpected"):
        # a partial match means the model/optimizer no longer lines up
        # with the checkpoint (renamed layer, resized net): params left
        # at fresh-init while step/LR/RNG resume late would diverge
        # SILENTLY — make it loud, but let intentional surgery proceed
        import warnings
        warnings.warn(
            "checkpoint restore is PARTIAL: "
            f"{len(cov.get('missing', []))} trainer key(s) absent from "
            f"the checkpoint (e.g. {cov.get('missing', ['-'])[:3]}), "
            f"{len(cov.get('unexpected', []))} checkpoint key(s) the "
            f"trainer didn't ask for (e.g. "
            f"{cov.get('unexpected', ['-'])[:3]}); the resume is NOT "
            "bit-exact", RuntimeWarning)
    _, meta = got
    trainer.load_train_state(
        {k: t.value for k, t in wrapped.items()}, meta)
    note_elastic_resume(meta, step=meta.get("step_count"))
    return meta


def note_elastic_resume(meta, step=None):
    """Detect and announce a resume at a DIFFERENT world size than the
    checkpoint was saved at (the elastic shrink/grow path): emits the
    `fleet.elastic` telemetry event + counter `tools/fleet_report.py`
    renders.  Returns (old_world, new_world) when they differ, else
    None.  The restore itself needs nothing special — reshard-on-load
    is the default contract — this is the observability half."""
    old = (meta or {}).get("world")
    if old is None:
        return None
    new = _proc_rank_world()[1]
    if int(old) == int(new):
        return None
    from ... import telemetry as _tel
    _tel.counter("fleet.elastic_resumes").inc()
    _tel.emit("fleet.elastic", phase="resume", old_world=int(old),
              new_world=int(new), step=step,
              cursor=(meta or {}).get("data_cursor"))
    import warnings
    warnings.warn(
        f"elastic resume: checkpoint saved at world {old}, restoring "
        f"at world {new} (reshard-on-load)", RuntimeWarning)
    return int(old), new


# ---------------------------------------------------------------------------
# topology-aware data cursor plumbing (io.ElasticDataCursor)
# ---------------------------------------------------------------------------

def cursor_to_meta(owner, meta):
    """Fold an attached data cursor (`owner.attach_data_cursor`) into a
    train_state meta dict: the (epoch, global_sample_offset) pair is
    topology-independent, so a job resumed at a new dp degree replays
    exactly the unseen samples."""
    cur = getattr(owner, "_data_cursor", None)
    if cur is not None:
        meta["data_cursor"] = dict(cur.state_dict())
    return meta


def cursor_from_meta(owner, meta):
    """Restore an attached data cursor from a train_state meta dict
    (no-op when either side is absent)."""
    cur = getattr(owner, "_data_cursor", None)
    state = (meta or {}).get("data_cursor")
    if cur is not None and state:
        cur.load_state_dict(dict(state))
