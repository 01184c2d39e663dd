"""Streaming artifact writer: quantize one kernel at a time, commit as you go.

Memory posture: the walk holds host copies of *one* leaf's buffers at a time
(plus the transient dequantized copy used for the error stat), so the writer's
peak incremental host allocation is O(largest kernel), not O(model) — asserted
by ``tests/test_artifacts.py`` with tracemalloc.

Durability posture (same idiom as ``runtime/checkpoint.py``):

  * data is appended to shard files under ``<out>.staging/``; every
    ``commit_every`` tensors (group commit) the dirty shards are fsync'd
    and *then* the staging manifest is atomically replaced (tmp +
    ``os.replace``) — a tensor is *committed* iff it appears in the
    on-disk staging manifest, which only ever advances after the data it
    references is durable;
  * a crash mid-group leaves at worst an uncommitted tail past the last
    committed shard length; resume truncates it and re-quantizes only the
    tensors of the torn group (committed ones are ``skipped`` in the
    progress stream);
  * ``finalize()`` flushes any pending group, marks the manifest complete
    and ``os.rename``s the staging directory onto the final path — readers
    never observe a partial artifact.

``commit_every=1`` recovers the PR-3 per-tensor fsync behavior (maximum
resume granularity); the default batches fsyncs, removing the write path's
main durability overhead (1.18x over per-tensor at smoke scale) and
bringing streaming quantization to parity with the in-memory tree walk
(measured in ``benchmarks/bench_artifacts.py``).
"""

from __future__ import annotations

import json
import os
import shutil
import time
from pathlib import Path
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

import numpy as np

from repro.artifacts import format as afmt
from repro.artifacts.format import (MANIFEST_NAME, ArtifactError,
                                    align_up, buffer_record)
from repro.core.quantize_model import QuantizedKernel

ProgressFn = Callable[[Dict[str, Any]], None]


def _fsync_dir(path: Path):
    """Durably persist a directory entry (rename/replace targets)."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return  # platform without directory fds — best effort
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


class ArtifactWriter:
    """Incremental, resumable writer for one artifact directory."""

    DEFAULT_COMMIT_EVERY = 8

    def __init__(self, out_dir: str | Path, *, arch: str,
                 model_config: Dict[str, Any], ptqtp_config: Dict[str, Any],
                 resume: bool = True, overwrite: bool = False,
                 shard_max_bytes: int = 1 << 28,
                 commit_every: Optional[int] = None):
        self.final = Path(out_dir)
        self.stage = self.final.with_name(self.final.name + ".staging")
        self.shard_max_bytes = int(shard_max_bytes)
        self.commit_every = max(1, int(commit_every
                                       if commit_every is not None
                                       else self.DEFAULT_COMMIT_EVERY))
        self._pending = 0        # tensors appended since the last durable commit
        self._dirty: set = set()  # shard files with appended-but-unfsynced data
        # An existing artifact is only replaced at finalize() — a crash
        # mid-quantize must never destroy the fleet's last good artifact.
        self._overwrite = overwrite
        if self.final.exists() and not overwrite:
            raise ArtifactError(
                f"artifact already exists: {self.final} "
                "(pass overwrite=True / --overwrite to replace)")
        if overwrite and self.stage.exists():  # overwrite restarts cleanly
            shutil.rmtree(self.stage)

        # JSON-canonical header (tuples → lists, etc.) so a resume compares
        # equal against the manifest it reads back from disk
        header = json.loads(json.dumps({
            "format": afmt.FORMAT_NAME,
            "format_version": afmt.FORMAT_VERSION,
            "arch": arch,
            "model_config": model_config,
            "ptqtp_config": ptqtp_config,
        }))
        if resume and (self.stage / MANIFEST_NAME).exists():
            self.manifest = self._resume(header)
        else:
            if self.stage.exists():
                shutil.rmtree(self.stage)
            self.stage.mkdir(parents=True)
            self.manifest = dict(header, complete=False, created=time.time(),
                                 shards=[], tensors={})
            # commit the header immediately: even under group commit (where
            # tensor commits are batched) a staging dir always records the
            # config it was written with, so resume can reject mismatches
            self._commit_manifest()

    # ------------------------------------------------------------- resume
    def _resume(self, header: Dict[str, Any]) -> Dict[str, Any]:
        with open(self.stage / MANIFEST_NAME) as f:
            manifest = json.load(f)
        for key, want in header.items():
            if manifest.get(key) != want:
                raise ArtifactError(
                    f"staging dir {self.stage} was written with a different "
                    f"{key!r} (have {manifest.get(key)!r}, want {want!r}); "
                    "remove it or pass overwrite=True to restart")
        # Drop any torn tail past the last committed tensor: the manifest's
        # per-shard nbytes only advances on commit, so truncating to it makes
        # the shard byte-exact with the committed record set.
        for rec in manifest["shards"]:
            p = self.stage / rec["file"]
            if not p.exists() or p.stat().st_size < rec["nbytes"]:
                raise ArtifactError(
                    f"shard {p} is shorter than its committed length "
                    f"({rec['nbytes']}); staging dir is corrupt — remove it")
            os.truncate(p, rec["nbytes"])
        return manifest

    # ------------------------------------------------------------ internals
    def _shard_for(self, nbytes: int) -> Dict[str, Any]:
        """Current shard record, rolling to a new file when adding `nbytes`
        would push the current one past shard_max_bytes (tensors never
        split across shards)."""
        shards = self.manifest["shards"]
        if shards and (shards[-1]["nbytes"] + nbytes <= self.shard_max_bytes
                       or shards[-1]["nbytes"] == 0):
            return shards[-1]
        rec = {"file": f"shard_{len(shards):05d}.bin", "nbytes": 0}
        (self.stage / rec["file"]).touch()
        shards.append(rec)
        return rec

    def _append_buffers(self, arrays: Dict[str, np.ndarray]
                        ) -> Dict[str, Dict[str, Any]]:
        """Append host arrays to the current shard; returns buffer records.
        The shard record's nbytes is only advanced here (in memory) — it
        reaches disk with the manifest commit, after the data is fsync'd
        (possibly a few tensors later, under group commit)."""
        total = sum(align_up(a.nbytes) for a in arrays.values())
        shard = self._shard_for(total)
        records = {}
        with open(self.stage / shard["file"], "r+b") as f:
            f.seek(shard["nbytes"])
            off = shard["nbytes"]
            for name, arr in arrays.items():
                arr = np.ascontiguousarray(arr)
                pad = align_up(off) - off
                if pad:
                    f.write(b"\0" * pad)
                    off += pad
                records[name] = buffer_record(shard["file"], off, arr)
                f.write(afmt.byte_view(arr))
                off += arr.nbytes
            f.flush()
        shard["nbytes"] = off
        self._dirty.add(shard["file"])
        return records

    def _tensor_added(self):
        """Group-commit bookkeeping: count the tensor, flush every N."""
        self._pending += 1
        if self._pending >= self.commit_every:
            self._commit_group()

    def _commit_group(self):
        """Make everything appended so far durable: fsync dirty shards
        first, then (and only then) advance the on-disk manifest — the
        commit invariant the resume path relies on."""
        for name in sorted(self._dirty):
            fd = os.open(self.stage / name, os.O_RDONLY)
            try:
                os.fsync(fd)
            finally:
                os.close(fd)
        self._dirty.clear()
        self._commit_manifest()
        self._pending = 0

    def _commit_manifest(self):
        # fsync file-then-dir so "committed iff in the manifest" holds even
        # across power loss: the replace must never land with torn content
        tmp = self.stage / (MANIFEST_NAME + ".tmp")
        with open(tmp, "w") as f:
            json.dump(self.manifest, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self.stage / MANIFEST_NAME)
        _fsync_dir(self.stage)

    # ------------------------------------------------------------------ API
    def committed(self, path: str) -> bool:
        return path in self.manifest["tensors"]

    def add_fp(self, path: str, arr) -> None:
        """Commit one unquantized FP leaf."""
        arr = np.asarray(arr)
        bufs = self._append_buffers({"data": arr})
        self.manifest["tensors"][path] = {"kind": "fp", "buffers": bufs}
        self._tensor_added()

    def add_quantized(self, path: str, qk: QuantizedKernel, *,
                      source_shape: Tuple[int, ...], source_dtype: str,
                      error: Optional[Dict[str, float]] = None) -> None:
        """Commit one quantized kernel (packed planes + scales + meta/stats)."""
        arrays = {name: np.asarray(getattr(qk, name))
                  for name in afmt.QK_BUFFERS}
        bufs = self._append_buffers(arrays)
        self.manifest["tensors"][path] = {
            "kind": "ptqtp",
            "meta": {"d_in": qk.d_in, "d_out": qk.d_out,
                     "group_size": qk.group_size},
            "source": {"shape": list(source_shape), "dtype": source_dtype},
            "error": error or {},
            "buffers": bufs,
        }
        self._tensor_added()

    def finalize(self) -> Path:
        """Compute summary stats, mark complete, atomically publish."""
        stats = {"n_tensors": 0, "n_quantized": 0, "fp_bytes": 0,
                 "quantized_bytes": 0, "quantized_weight_count": 0,
                 "source_fp16_bytes": 0}
        for rec in self.manifest["tensors"].values():
            stats["n_tensors"] += 1
            nbytes = sum(b["nbytes"] for b in rec["buffers"].values())
            if rec["kind"] == "ptqtp":
                stats["n_quantized"] += 1
                stats["quantized_bytes"] += nbytes
                n_w = int(np.prod(rec["source"]["shape"]))
                stats["quantized_weight_count"] += n_w
                stats["source_fp16_bytes"] += n_w * 2
            else:
                stats["fp_bytes"] += nbytes
        stats["total_bytes"] = stats["fp_bytes"] + stats["quantized_bytes"]
        if stats["quantized_weight_count"]:
            stats["bytes_per_weight"] = (stats["quantized_bytes"]
                                         / stats["quantized_weight_count"])
        self.manifest["stats"] = stats
        self.manifest["complete"] = True
        self.manifest["finalized"] = time.time()
        self._commit_group()  # flush any pending tensors with the final commit
        if self.final.exists():
            if not self._overwrite:
                raise ArtifactError(
                    f"artifact appeared at {self.final} during the write "
                    "(pass overwrite=True / --overwrite to replace it)")
            shutil.rmtree(self.final)  # old artifact survives until here
        os.rename(self.stage, self.final)
        _fsync_dir(self.final.parent)
        return self.final


# ---------------------------------------------------------------------------
# streaming quantization driver
# ---------------------------------------------------------------------------

def write_artifact(out_dir: str | Path, *, arch: str, model_cfg, ptqtp_cfg,
                   params: Any, predicate=None, compute_error: bool = True,
                   progress: Optional[ProgressFn] = None, resume: bool = True,
                   overwrite: bool = False,
                   shard_max_bytes: int = 1 << 28,
                   commit_every: Optional[int] = None) -> Path:
    """Quantize a model into an artifact, one kernel at a time.

    ``params`` is either a nested-dict tree (walked lazily leaf by leaf) or
    an iterable of ``(path, leaf)`` pairs — e.g.
    :func:`iter_checkpoint_leaves`, which streams straight out of a training
    checkpoint so the FP tree is never materialized in host memory at all.
    Tensors already committed in a staging manifest are skipped (resume).
    ``commit_every`` sets the fsync group-commit size (1 → per-tensor
    durability, default ``ArtifactWriter.DEFAULT_COMMIT_EVERY``).
    """
    from repro.core import ptqtp as ptqtp_mod
    from repro.core.quantize_model import (default_predicate, quantize_kernel,
                                           relative_error)

    cfg = ptqtp_cfg or ptqtp_mod.PTQTPConfig()
    predicate = predicate or default_predicate
    writer = ArtifactWriter(
        out_dir, arch=arch,
        model_config=afmt.model_config_to_json(model_cfg),
        ptqtp_config=afmt.ptqtp_config_to_json(cfg),
        resume=resume, overwrite=overwrite, shard_max_bytes=shard_max_bytes,
        commit_every=commit_every)

    leaves: Iterable[Tuple[str, Any]]
    leaves = afmt.iter_tree_leaves(params) if isinstance(params, dict) \
        else params
    t0 = time.time()
    for idx, (path, leaf) in enumerate(leaves):
        info = {"index": idx, "path": path,
                "shape": tuple(np.shape(leaf)), "elapsed": time.time() - t0}
        if writer.committed(path):
            progress and progress(dict(info, action="skip"))
            continue
        if predicate(path, leaf, cfg.group_size):
            qk = quantize_kernel(leaf, cfg)
            error = None
            if compute_error:
                error = {"rel_fro_error": float(relative_error(leaf, qk))}
            writer.add_quantized(
                path, qk, source_shape=tuple(np.shape(leaf)),
                source_dtype=str(getattr(leaf, "dtype", "float32")),
                error=error)
            progress and progress(dict(info, action="quantize", error=error))
        else:
            writer.add_fp(path, leaf)
            progress and progress(dict(info, action="fp"))
    return writer.finalize()


def iter_checkpoint_leaves(ckpt_dir: str | Path, subtree: str = "params"
                           ) -> Iterable[Tuple[str, Any]]:
    """Stream FP leaves lazily out of a ``runtime/checkpoint.py`` checkpoint.

    ``np.load`` on an npz decompresses arrays on access, so this holds one
    tensor at a time — the quantize-from-checkpoint path never needs the
    model in host RAM twice (or even once, fully).
    """
    from repro.runtime.checkpoint import _SEP, latest_step

    ckpt_dir = Path(ckpt_dir)
    step = latest_step(ckpt_dir)
    if step is None:
        raise FileNotFoundError(f"no LATEST in {ckpt_dir}")
    d = ckpt_dir / f"step_{step:08d}"
    prefix = f"{subtree}{_SEP}"
    for shard in sorted(d.glob("host*.npz")):
        with np.load(shard) as z:
            for key in z.files:
                if not key.startswith(prefix):
                    continue
                path = "/" + key[len(prefix):].replace(_SEP, "/")
                yield path, z[key]
