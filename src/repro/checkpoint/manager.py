"""Fault-tolerant checkpointing with GPULZ-compressed shards.

Layout:  <dir>/step_<N>/
             manifest.json       tree structure, shapes, dtypes, per-leaf CRC
             COMMIT              commit marker, written LAST before publish
             <leaf-id>.gplz      GPULZ container  (or .raw if compression off)
         <dir>/step_<N>.tmp...   staging dir, atomically renamed on success

Fault-tolerance properties:
  * atomic publish (tmp dir + os.rename) — a crash mid-save never corrupts
    the latest checkpoint;
  * commit-marker discipline: blobs -> manifest -> ``COMMIT`` -> rename, in
    that order.  ``steps()`` lists only marker-bearing dirs, so a
    half-written step (crash at ANY boundary, or a hand-planted marker-less
    dir) is never restorable, never counts toward retention, and never
    blocks GC of older complete steps — ``_gc`` removes such debris once no
    writer owns it.  (Pre-marker-era checkpoints are treated as
    uncommitted debris: re-save them.)
  * ``async_writes=True`` hands every byte to a double-buffered background
    writer (``runtime/async_io.AsyncBlobWriter``): ``save`` overlaps each
    dtype-class compression dispatch with the previous group's host write
    and returns before the step is durable.  A background failure surfaces
    on the NEXT ``save``/``wait_until_finished`` as an ``AsyncWriteError``
    naming the step and path; an in-flight step is never GC'd; writer
    backpressure is exported for ``StepGuard`` accounting.  With async off
    (default) the write path is host-synchronous exactly as before and the
    on-disk bytes are identical either way;
  * every write goes through the ``runtime/fault.HostFS`` seam under a
    ``RetryPolicy`` (transient-EIO retry with backoff; ENOSPC fails fast),
    so the crash/fault harness can inject failures at exact boundaries;
  * every leaf CRC-checked on restore; a damaged step is skipped and the
    previous valid step restored (``restore_latest``);
  * checkpoints are mesh-agnostic: leaves are stored as full logical arrays
    and re-device_put under the *target* mesh's shardings on restore —
    elastic restarts onto a different mesh shape are free (runtime/elastic.py);
  * symbol size picked per dtype (S=4 fp32/int32, S=2 bf16/f16/int16), the
    paper's multi-byte rule;
  * leaves of a dtype class are compressed together: one batched pipeline
    dispatch (``lzss.compress_many``) per (symbol size, chunk-count bucket)
    group instead of one ``compress()`` call per leaf;
  * with ``lz_mesh=...`` that dispatch is shard-mapped over the mesh's batch
    axis (``sharding/batch.py``, the ``"sharded"`` registry pair).  Blobs are
    byte-identical to the single-device dispatch, so checkpoints stay
    mesh-agnostic: a step written on an 8-device mesh restores on 2 devices
    (or 1) — ``runtime/elastic.py`` re-points ``lz_mesh`` at the
    restore-side mesh.
"""

from __future__ import annotations

import dataclasses
import json
import os
import zlib

import jax
import numpy as np

from repro.core import lzss
from repro.runtime.async_io import AsyncBlobWriter, RetryPolicy
from repro.runtime.fault import HostFS

COMMIT_MARKER = "COMMIT"
# raw bytes per compression dispatch: the codec's device working buffers
# scale with it (the TPU kernel stages an int32 per container byte)
DISPATCH_BYTES = 256 * 2**20


def _symbol_size(dtype: np.dtype) -> int:
    return {4: 4, 2: 2, 1: 1}.get(np.dtype(dtype).itemsize, 4)


def _dispatches(idxs, sizes):
    """Split one geometry group into dispatches of <= DISPATCH_BYTES each
    (in order; a leaf larger than that is a dispatch of its own)."""
    out, cur, held = [], [], 0
    for i, n in zip(idxs, sizes):
        if cur and held + n > DISPATCH_BYTES:
            out.append(cur)
            cur, held = [], 0
        cur.append(i)
        held += n
    return out + [cur] if cur else out


def _leaf_paths(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    names = ["/".join(str(getattr(k, "key", k)) for k in path) for path, _ in flat]
    return names, [leaf for _, leaf in flat], treedef


@dataclasses.dataclass
class CheckpointManager:
    directory: str
    compress: bool = True
    keep: int = 3
    lz_window: int = 64
    # the codec's default chunk (LZSSConfig): restores share the codec's
    # tuned decode geometry, and every distinct leaf shape compiles a kernel
    # on its first save and restore, which at C=4096 is the slower compile
    # (the first save of a 4-layer llama3.2-1b-width train state took 375 s
    # on a v5e at C=4096, compiles included)
    lz_chunk: int = 2048
    lz_backend: str = "auto"   # compressor registry key; "auto" = the
                               # single-kernel fused-mono pipeline on TPU
    lz_decoder: str = "auto"   # decode registry key; "auto" = the single-
                               # launch fused-mono decoder on TPU (restores
                               # decode straight from the stored blobs)
    lz_chunks_per_block: object = None  # kernel block geometry for BOTH
                               # save and restore kernels; None = the
                               # core/autotune.py chooser per device
    lz_mesh: object = None     # shard each per-dtype-class batched dispatch
                               # over this mesh ("sharded" registry pair);
                               # blobs on disk stay byte-identical, so a
                               # checkpoint written on one mesh restores on
                               # any other (runtime/elastic.py re-points
                               # lz_mesh at the restore-side mesh)
    lz_batch_axis: object = None
    lz_lossy_eb: object = None  # error-bounded lossy compression of f32
                               # leaves (lossy-fz codec: each restored
                               # element within eb of the saved value,
                               # non-finite exact); every other dtype — and
                               # all leaves when None — stays lossless.
                               # Lossy leaves CRC the stored blob instead of
                               # the raw bytes (the raw bytes are not
                               # reproduced bit-exactly by design).
    async_writes: bool = False  # hand blob/manifest/commit writes to the
                               # double-buffered background writer; save()
                               # returns before the step is durable and a
                               # write failure surfaces on the NEXT save /
                               # wait_until_finished (AsyncWriteError)
    fs: object = None          # runtime/fault.HostFS seam (FaultyFS in the
                               # crash/fault-injection harness)
    writer: object = None      # injectable AsyncBlobWriter; lazily built
    io_retry: object = None    # runtime/async_io.RetryPolicy for host
                               # writes in BOTH modes (transient-EIO retry)
    io_max_pending: int = 2    # async double-buffer depth: how many steps
                               # may be in flight before save() blocks

    def __post_init__(self):
        if self.fs is None:
            self.fs = HostFS()
        if self.io_retry is None:
            self.io_retry = RetryPolicy()
        # backpressure of the most recent async save() (seconds the call
        # blocked waiting for writer queue room) — StepGuard's io signal
        self.last_save_io_wait_s = 0.0

    # ------------------------------------------------------------- save

    def _get_writer(self) -> AsyncBlobWriter:
        if self.writer is None:
            self.writer = AsyncBlobWriter(
                fs=self.fs, max_pending_steps=self.io_max_pending,
                retry=self.io_retry,
            )
        return self.writer

    def wait_until_finished(self):
        """Block until every async write has landed; re-raise any
        background failure (AsyncWriteError naming step and path)."""
        if self.writer is not None:
            self.writer.wait_until_finished()

    def writer_stats(self) -> dict:
        return self.writer.stats() if self.writer is not None else {}

    def _lz_config(self, symbol_size: int, lossy: bool = False) -> "lzss.LZSSConfig":
        # "auto" backend/decoder resolve per-platform at dispatch time;
        # with a mesh they map to the shard-mapped "sharded" pair instead
        backend, decoder = self.lz_backend, self.lz_decoder
        if lossy:
            # configured backend becomes the lossy container's inner
            # lossless stage (mirrors optim/grad_compress.lossy_grad_config)
            inner = "auto" if backend in ("lossy-fz", "sharded") else backend
            if self.lz_mesh is not None:
                decoder = "sharded" if decoder == "auto" else decoder
            return lzss.LZSSConfig(
                symbol_size=4, window=self.lz_window,
                chunk_symbols=self.lz_chunk,
                chunks_per_block=self.lz_chunks_per_block,
                backend="lossy-fz", decoder=decoder,
                lossy_eb=float(self.lz_lossy_eb), lossy_inner=inner,
                mesh=self.lz_mesh, batch_axis=self.lz_batch_axis,
            )
        if self.lz_mesh is not None:
            backend = "sharded" if backend == "auto" else backend
            decoder = "sharded" if decoder == "auto" else decoder
        return lzss.LZSSConfig(
            symbol_size=symbol_size, window=self.lz_window,
            chunk_symbols=self.lz_chunk,
            chunks_per_block=self.lz_chunks_per_block, backend=backend,
            decoder=decoder, mesh=self.lz_mesh,
            batch_axis=self.lz_batch_axis,
        )

    def save(self, state, step: int) -> str:
        """Write one step.  Sync mode publishes before returning; async
        mode enqueues blobs group by group — the NEXT group's compression
        dispatch overlaps the previous group's host write — and returns
        once the commit op is queued (the step publishes in the
        background, in enqueue order)."""
        fs = self.fs
        final = os.path.join(self.directory, f"step_{step:08d}")
        tmp = final + ".tmp"
        writer = None
        self.last_save_io_wait_s = 0.0
        if self.async_writes:
            # begin_step re-raises any prior background failure (the
            # surfaced-on-next-save contract) and blocks while the
            # double-buffer window is full (measured backpressure)
            writer = self._get_writer()
            self.last_save_io_wait_s = writer.begin_step(step)
        fs.makedirs(self.directory, exist_ok=True)
        if fs.exists(tmp):
            fs.rmtree(tmp)
        fs.makedirs(tmp)

        if writer is None:
            def emit(fname: str, data) -> None:
                path = os.path.join(tmp, fname)
                self.io_retry.run(lambda: fs.write_bytes(path, data))
        else:
            def emit(fname: str, data) -> None:
                writer.put_write(step, os.path.join(tmp, fname), data)

        names, leaves, _ = _leaf_paths(state)
        manifest = {"step": step, "leaves": []}
        entries, raws = [], []
        groups: dict = {}  # (S, chunk-count bucket) -> leaf indices
        for i, (name, leaf) in enumerate(zip(names, leaves)):
            arr = np.asarray(jax.device_get(leaf))
            raw = arr.tobytes()
            raws.append(raw)
            fname = name.replace("/", ".") or "scalar"
            entries.append({
                "name": name,
                "shape": list(arr.shape),
                "dtype": str(arr.dtype),
                "crc32": zlib.crc32(raw),
                "nbytes": len(raw),
                "file": fname,
            })
            if self.compress and len(raw) >= 1024:
                lossy = (
                    self.lz_lossy_eb is not None
                    and arr.dtype == np.float32
                )
                s = _symbol_size(arr.dtype)
                nsym = -(-len(raw) // s)
                nc = -(-nsym // self.lz_chunk)
                # bucket by chunk count so a tiny leaf is never padded to a
                # huge leaf's geometry inside the shared batch
                bucket = 1 << max(0, nc - 1).bit_length()
                groups.setdefault((s, bucket, lossy), []).append(i)
            else:
                entries[i]["codec"] = "raw"
                entries[i]["stored_bytes"] = len(raw)
                entries[i]["file"] = fname + ".raw"
                emit(entries[i]["file"], raw)
        # batched compression dispatches per dtype-class group, each holding
        # at most DISPATCH_BYTES of raw leaves (a larger leaf goes alone) so
        # the device's working buffers stay bounded; in async mode each
        # dispatch's blobs are queued as soon as it returns, so dispatch k's
        # host writes overlap dispatch k+1's compression
        for (s, _bucket, lossy), idxs in groups.items():
            cfg = self._lz_config(s, lossy=lossy)
            for sub in _dispatches(idxs, [len(raws[i]) for i in idxs]):
                batch = lzss.compress_many(
                    [np.frombuffer(raws[i], np.uint8) for i in sub], cfg
                )
                for j, i in enumerate(sub):
                    res = batch[j]
                    entries[i]["codec"] = "gpulz"
                    entries[i]["stored_bytes"] = res.total_bytes
                    entries[i]["file"] += ".gplz"
                    if lossy:
                        # the restored bytes differ from `raw` by design, so
                        # the raw CRC cannot gate restore; CRC the stored
                        # container instead (still catches disk corruption
                        # before decode)
                        entries[i]["lossy"] = True
                        entries[i]["crc32"] = zlib.crc32(res.data.tobytes())
                    emit(entries[i]["file"], res.data.tobytes())
        manifest["leaves"] = entries
        emit("manifest.json", json.dumps(manifest).encode())
        # the commit marker is written LAST: a crash at any earlier
        # boundary leaves a marker-less dir that steps()/restore/GC treat
        # as nonexistent debris
        emit(COMMIT_MARKER, b"")
        if writer is None:
            if fs.exists(final):
                fs.rmtree(final)
            self.io_retry.run(lambda: fs.rename(tmp, final))
            self._gc()
        else:
            writer.put_commit(step, tmp, final, after=self._gc)
        return final

    # ---------------------------------------------------------- restore

    def steps(self):
        """Committed steps only: a dir without its COMMIT marker (crash
        debris, a hand-planted partial, an in-flight async publish) is
        never listed and therefore never restorable."""
        fs = self.fs
        if not fs.isdir(self.directory):
            return []
        out = []
        for d in fs.listdir(self.directory):
            if d.startswith("step_") and not d.endswith(".tmp"):
                if not fs.exists(
                    os.path.join(self.directory, d, COMMIT_MARKER)
                ):
                    continue
                try:
                    out.append(int(d[5:]))
                except ValueError:
                    pass
        return sorted(out)

    def _load_step(self, template, step: int, shardings=None):
        d = os.path.join(self.directory, f"step_{step:08d}")
        with open(os.path.join(d, "manifest.json")) as f:
            manifest = json.load(f)
        by_name = {e["name"]: e for e in manifest["leaves"]}
        names, leaves, treedef = _leaf_paths(template)
        sh_leaves = (
            jax.tree_util.tree_leaves(shardings) if shardings is not None
            else [None] * len(leaves)
        )
        # batched restore: one decompression dispatch per container geometry
        blobs, geom_groups = {}, {}
        for name in names:
            e = by_name[name]
            if e["codec"] != "gpulz":
                continue
            blob = np.fromfile(os.path.join(d, e["file"]), np.uint8)
            if e.get("lossy") and zlib.crc32(blob.tobytes()) != e["crc32"]:
                # lossy leaves CRC the stored container (the raw bytes are
                # not reproduced bit-exactly); verify before decode
                raise IOError(f"CRC mismatch for {name} at step {step}")
            h = lzss.fmt.parse_header(blob)
            blobs[name] = blob
            # version + method byte join the batching key so a checkpoint
            # holding both lossless and lossy-fz leaves never lands a
            # mixed-method batch in one decompress_many call; lossy blobs
            # additionally split on their static decode params
            geom_groups.setdefault(
                (h.version, h.method, h.symbol_size, h.chunk_symbols,
                 h.n_chunks, h.lossy_mode, h.inner_method), []
            ).append(name)
        decompressed = {}
        # an explicitly non-sharded lz_decoder + lz_mesh means compress-side
        # sharding only: restore single-device rather than conflicting
        sharded = self.lz_decoder in ("auto", "sharded")
        method_only = {
            lzss.fmt.METHOD_HUFFMAN: "deflate-full",
            lzss.fmt.METHOD_LOSSY: "lossy-fz",
        }
        for gkey, group in geom_groups.items():
            decoder = self.lz_decoder
            if decoder not in ("auto", "sharded") and decoder != \
                    method_only.get(gkey[1]) and (
                        decoder in method_only.values()
                        or gkey[1] in method_only
                    ):
                # decoder/method mismatch (e.g. a raw-method decoder pinned
                # while this group is lossy): fall back per group — the
                # container's method byte routes to the right decoder
                decoder = "auto"
            # the same raw-byte bound per dispatch as save
            for sub in _dispatches(group, [by_name[n]["nbytes"] for n in group]):
                raws = lzss.decompress_many(
                    [blobs[n] for n in sub], decoder=decoder,
                    mesh=self.lz_mesh if sharded else None,
                    batch_axis=self.lz_batch_axis if sharded else None,
                    # the pin governs restore kernels too, not just save
                    chunks_per_block=self.lz_chunks_per_block,
                )
                decompressed.update(
                    {n: r.tobytes() for n, r in zip(sub, raws)}
                )
        out = []
        for name, tmpl, sh in zip(names, leaves, sh_leaves):
            e = by_name[name]
            if e["codec"] == "gpulz":
                raw = decompressed[name]
            else:
                with open(os.path.join(d, e["file"]), "rb") as f:
                    raw = f.read()
            if not e.get("lossy") and zlib.crc32(raw) != e["crc32"]:
                raise IOError(f"CRC mismatch for {name} at step {step}")
            arr = np.frombuffer(raw, e["dtype"]).reshape(e["shape"])
            if sh is not None:
                out.append(jax.device_put(arr, sh))
            else:
                out.append(arr)
        return jax.tree_util.tree_unflatten(treedef, out), manifest["step"]

    def restore(self, template, step: int, shardings=None):
        return self._load_step(template, step, shardings)

    def restore_latest(self, template, shardings=None):
        """Walk back from the newest step until one restores cleanly.

        Only damaged data makes a step unusable: a missing or unreadable
        file, a manifest that does not parse or lacks a leaf (``KeyError``),
        a CRC mismatch (``IOError``) or a container that fails validation
        (``ValueError``).  Anything else — a decode kernel that cannot
        compile, a device error — propagates instead of reading as "no
        checkpoint"."""
        for step in reversed(self.steps()):
            try:
                return self._load_step(template, step, shardings)
            except (OSError, ValueError, KeyError) as exc:
                print(f"[ckpt] step {step} unusable ({exc}); trying older")
        return None, -1

    def _gc(self):
        """Retention GC, commit-marker- and in-flight-aware.

        * only COMMITTED steps count toward ``keep`` (a half-written step
          never blocks GC of older complete ones);
        * a step the async writer still owns — registered but not yet
          renamed — is never deleted, nor is its staging dir;
        * marker-less ``step_*`` dirs and stale ``*.tmp`` dirs (crash
          debris) are swept once no writer owns them.

        Runs on the worker thread after each async commit (FIFO queue =
        the rename happened-before this GC) and inline after sync saves.
        """
        fs = self.fs
        if not fs.isdir(self.directory):
            return
        inflight = (
            self.writer.in_flight() if self.writer is not None else set()
        )
        protected = set()
        for s in inflight:
            protected.add(f"step_{s:08d}")
            protected.add(f"step_{s:08d}.tmp")
        for s in self.steps()[: -self.keep]:
            name = f"step_{s:08d}"
            if name in protected:
                continue
            fs.rmtree(os.path.join(self.directory, name), ignore_errors=True)
        for d in fs.listdir(self.directory):
            if not d.startswith("step_") or d in protected:
                continue
            path = os.path.join(self.directory, d)
            if not fs.isdir(path):
                continue
            if d.endswith(".tmp") or not fs.exists(
                os.path.join(path, COMMIT_MARKER)
            ):
                fs.rmtree(path, ignore_errors=True)

    def stats(self, step: int) -> dict:
        d = os.path.join(self.directory, f"step_{step:08d}")
        with open(os.path.join(d, "manifest.json")) as f:
            manifest = json.load(f)
        orig = sum(e["nbytes"] for e in manifest["leaves"])
        stored = sum(e["stored_bytes"] for e in manifest["leaves"])
        return {
            "orig_bytes": orig,
            "stored_bytes": stored,
            "ratio": orig / max(1, stored),
        }
