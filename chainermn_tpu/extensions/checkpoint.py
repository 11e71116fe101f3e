"""Distributed checkpointing with generation GC and auto-resume.

Reference being rebuilt (path unverified, SURVEY.md provenance):
``create_multi_node_checkpointer`` in 〔chainermn/extensions/checkpoint.py〕
— each rank saves its own state under a shared name/path, old generations
are garbage-collected, and on startup ``resume()`` restores all ranks from
the latest generation present on *every* rank (crash recovery for long
multi-node runs; the reference's only failure-recovery mechanism —
fail-stop + snapshot/resume, SURVEY.md §5.3, a posture this rebuild keeps).

TPU-native form: per-host npz files of the flattened state pytree
(``{path}/{name}.{iteration}.rank{r}.npz``); consistency of a generation is
agreed over the control plane (allgather of locally available generations,
intersect, take max).
"""

from __future__ import annotations

import json
import os
import re
import zipfile
from typing import Any, Dict, List, Optional, Set, Tuple

import jax
import numpy as np

from chainermn_tpu.utils.placement import local_device_put

# Sidecar keys persisted next to the leaf_{i} arrays in each npz: the
# FSDP sharding layout (world size + shard lengths) so a resume into a
# mismatched world fails loudly (ADVICE r5).  Underscored names cannot
# collide with leaf keys.
_FSDP_META_KEY = "__fsdp_meta__"
# Gradient-compression config of any error-feedback state in the tree
# (compressor specs + EF version): a resume under a different compressor
# would silently mis-scale the restored residuals.
_COMPRESSION_META_KEY = "__compression_meta__"
# Content hash + swap step of a hot-swapped plan table (the online
# tuner's step-boundary re-tune, planner/online.py): a resume that would
# silently execute a DIFFERENT plan than the run that saved must refuse
# — plan provenance is part of the run's performance contract.
_PLAN_TABLE_META_KEY = "__plan_table_meta__"


def _flatten_state(state) -> Tuple[dict, Any]:
    leaves, treedef = jax.tree.flatten(state)
    arrays = {f"leaf_{i}": np.asarray(jax.device_get(l))
              for i, l in enumerate(leaves)}
    return arrays, treedef


def _unflatten_state(arrays: dict, treedef, like_leaves: List[Any]):
    leaves = [arrays[f"leaf_{i}"] for i in range(len(like_leaves))]
    return jax.tree.unflatten(treedef, leaves)


def _in_live_dtype(new, old, name: str):
    """One restored host array in the LIVE leaf's dtype.  An npz keeps no
    extension dtype (bfloat16 comes back as two-byte void): the live leaf
    says what it was.  A float leaf saved wider or narrower than the live
    state holds it is cast, which is exact for the one state that changed
    so: the double buffer's ``pending`` was float32 until PR 46 and the
    exchange rounded it to the wire dtype at its first read.  Bytes that
    no dtype of the live leaf's size explains are refused by name."""
    dtype = getattr(old, "dtype", None)
    if dtype is None or new.dtype == dtype:
        return new
    dtype = np.dtype(dtype)
    if new.dtype.kind == "V":
        if new.dtype.itemsize != dtype.itemsize:
            raise ValueError(
                f"checkpoint leaf {name} holds {new.dtype.itemsize}-byte "
                f"values of a dtype numpy does not name (bfloat16 or an "
                f"fp8 saved through an npz) but the resume target holds "
                f"it as {dtype}: resume into a state built as the run "
                f"that saved was (the same wire dtype on the "
                f"communicator), or save it again from such a state")
        return new.view(dtype)
    if new.dtype.kind == "f" and jax.dtypes.issubdtype(dtype, np.floating):
        return new.astype(dtype)
    return new


def _place_like(new, old):
    """Place one restored host array with the LIVE leaf's sharding.
    Restores must never cross processes — every rank's npz holds what
    its own devices need — so this rides ``local_device_put`` (see
    utils/placement.py for the gloo interleaving hazard a plain
    ``jax.device_put`` carries on multi-controller meshes)."""
    shd = getattr(old, "sharding", None)
    if shd is None:
        return new
    return local_device_put(new, shd)


class _MultiNodeCheckpointer:
    def __init__(self, comm, path: str, name: str, keep: int = 2):
        self.comm = comm
        self.path = path
        self.name = name
        self.keep = keep
        os.makedirs(path, exist_ok=True)

    # -- naming --------------------------------------------------------------
    def _file(self, iteration: int, rank: Optional[int] = None) -> str:
        r = self.comm.rank if rank is None else rank
        return os.path.join(self.path,
                            f"{self.name}.{iteration}.rank{r}.npz")

    def _local_generations(self) -> List[int]:
        pat = re.compile(
            rf"^{re.escape(self.name)}\.(\d+)\.rank{self.comm.rank}\.npz$")
        gens = []
        for f in os.listdir(self.path):
            m = pat.match(f)
            if m:
                gens.append(int(m.group(1)))
        return sorted(gens)

    # -- save / GC -----------------------------------------------------------
    def _snapshot_arrays(self, state) -> dict:
        """Device->host copy plus sidecar capture — the only part of a
        save that must happen at the step boundary.  Returns the full
        npz payload (leaf arrays + layout/compression/plan-table
        sidecars); :meth:`_persist` can then write it from any thread
        (the async backend's split — elastic/async_ckpt.py)."""
        from chainermn_tpu.parallel.fsdp import fsdp_layout

        arrays, _ = _flatten_state(state)
        layout = fsdp_layout(state)
        if layout is not None:
            # persist the FsdpMeta-derived layout so resume() can
            # validate world size / mode before touching the arrays
            arrays[_FSDP_META_KEY] = np.array(json.dumps(layout))
        from chainermn_tpu.compression import compression_layout
        clayout = compression_layout(state)
        if clayout is not None:
            # ditto for error-feedback compression state (FSDP
            # bucket compressors or a compressed optimizer)
            arrays[_COMPRESSION_META_KEY] = np.array(
                json.dumps(clayout))
        from chainermn_tpu.planner.online import active_plan_table_meta
        tmeta = active_plan_table_meta()
        if tmeta is not None:
            # pin the hot-swapped plan table's hash so resume can
            # refuse a silently different plan (planner/online.py)
            arrays[_PLAN_TABLE_META_KEY] = np.array(json.dumps(tmeta))
        return arrays

    def _persist(self, arrays: dict, iteration: int):
        """Write + atomically publish one snapshot, then GC.  The GC
        runs strictly after ``os.replace`` — the write-barrier the async
        backend relies on: a generation can never be collected while the
        one superseding it is still a torn temp file."""
        # np.savez appends .npz when missing, so the temp name must
        # end in it
        tmp = self._file(iteration) + ".tmp.npz"
        np.savez(tmp, **arrays)
        os.replace(tmp, self._file(iteration))  # atomic publish
        self._gc()

    def save(self, state, iteration: int):
        from chainermn_tpu.observability import flight_recorder as _flight

        fr = _flight.get_flight_recorder()
        tok = None
        if fr is not None:
            tok = fr.span_begin("checkpoint", "checkpoint_save",
                                iteration=iteration)
        try:
            self._persist(self._snapshot_arrays(state), iteration)
        finally:
            if tok is not None:
                fr.span_end(tok)

    def _all_rank_generations(self) -> Dict[int, Set[int]]:
        """generation -> ranks with a published file, from one directory
        scan (all ranks, not just our own)."""
        pat = re.compile(
            rf"^{re.escape(self.name)}\.(\d+)\.rank(\d+)\.npz$")
        out: Dict[int, Set[int]] = {}
        for f in os.listdir(self.path):
            m = pat.match(f)
            if m:
                out.setdefault(int(m.group(1)), set()).add(int(m.group(2)))
        return out

    def _gc(self):
        """Collect generations past ``keep`` — but never one some rank
        in the *current* world still needs.  A crashed peer may be one
        or more generations behind: deleting our copy of the newest
        generation it shares with us would leave the world with no
        consistent generation at all.  So only generations strictly
        older than the newest generation *complete* across every rank
        visible in the directory (capped at ``comm.size`` — files from
        a larger pre-resize world don't pin anything) are collected.
        On per-host directories only our own rank is visible and this
        degrades to the plain keep-newest policy."""
        if not self.keep:
            return
        gens = self._local_generations()
        candidates = gens[:-self.keep]
        if not candidates:
            return
        by_gen = self._all_rank_generations()
        present: Set[int] = set()
        for ranks in by_gen.values():
            present |= {r for r in ranks if r < self.comm.size}
        complete = [g for g, ranks in by_gen.items()
                    if present and present <= ranks]
        newest_complete = max(complete) if complete else None
        for g in candidates:
            if newest_complete is not None and g >= newest_complete:
                # still (part of) the newest world-consistent
                # generation — a lagging peer resumes from here
                continue
            try:
                os.remove(self._file(g))
            except OSError:
                pass

    # -- resume --------------------------------------------------------------
    def _is_readable(self, fn: str) -> bool:
        """True when the npz at ``fn`` is a complete, CRC-clean zip.  A
        rank killed mid-write leaves its *previous* generation intact
        (the temp-rename publish), but a torn filesystem / truncated
        copy can still surface — such a file must not be offered as a
        resumable generation."""
        try:
            with zipfile.ZipFile(fn) as z:
                return z.testzip() is None
        except Exception:
            return False

    def latest_consistent_generation(self) -> Optional[int]:
        """Newest generation every rank holds a *readable* file for.
        Each rank CRC-checks its local candidates first (truncated or
        torn npz files are excluded before the vote), so one corrupted
        rank file degrades the answer to the previous complete
        generation instead of crashing the resume."""
        local = set(g for g in self._local_generations()
                    if self._is_readable(self._file(g)))
        all_gens = self.comm.allgather_obj(sorted(local))
        common = set(all_gens[0])
        for g in all_gens[1:]:
            common &= set(g)
        return max(common) if common else None

    def _validate_restore(self, arrays: dict, state, leaves, gen: int):
        """Refuse a world-size or sharding-mode mismatch BEFORE any leaf
        is restored (ADVICE r5: an FSDP checkpoint silently reloaded into
        a different world trains on garbage shards).  The supported
        cross-mode/cross-size path is exporting the full parameters with
        ``fsdp_full_params`` and re-sharding with ``fsdp_init``."""
        from chainermn_tpu.parallel.fsdp import fsdp_layout

        raw = arrays.pop(_FSDP_META_KEY, None)
        saved = json.loads(str(raw)) if raw is not None else None
        live = fsdp_layout(state)
        where = f"{self.name}.{gen} (rank {self.comm.rank})"
        if saved is not None and live is None:
            raise ValueError(
                f"checkpoint {where} holds an FSDP-sharded state "
                f"(world_size={saved['world_size']}) but the resume "
                f"target is unsharded — export full parameters via "
                f"fsdp_full_params(state, meta) before saving, or resume "
                f"into an FsdpState from fsdp_init on the same world")
        if saved is not None:
            if saved["world_size"] != self.comm.size:
                raise ValueError(
                    f"checkpoint {where} was saved with FSDP "
                    f"world_size={saved['world_size']} but this world has "
                    f"comm.size={self.comm.size}; shard layouts are bound "
                    f"to the world size — restore on a matching world, "
                    f"export with fsdp_full_params and re-shard with "
                    f"fsdp_init (the cross-size/cross-mode path), or, for "
                    f"inference, consolidate on the training world with "
                    f"consolidate_fsdp_checkpoint and load the full "
                    f"params with chainermn_tpu.serving.weights."
                    f"load_inference_params (world-size-free)")
            if "num_buckets" in saved \
                    and saved["num_buckets"] != live["num_buckets"]:
                raise ValueError(
                    f"checkpoint {where} was saved with "
                    f"num_buckets={saved['num_buckets']} but the live "
                    f"FsdpState was built with "
                    f"num_buckets={live['num_buckets']}; the bucketed "
                    f"shard layout is bound to the bucket config — pass "
                    f"the same num_buckets/bucket_bytes to fsdp_init "
                    f"before resuming, or export with fsdp_full_params "
                    f"and re-shard under the new config")
            if saved["shard_lens"] != live["shard_lens"]:
                raise ValueError(
                    f"checkpoint {where} shard layout "
                    f"{saved['shard_lens']} does not match the live "
                    f"FsdpState layout {live['shard_lens']} — the model "
                    f"or packing changed since the save")
        # Gradient-compression EF state: restoring residuals/scales saved
        # under a DIFFERENT compressor config would feed mis-scaled error
        # into every subsequent step — refuse with the fix spelled out
        # (mirrors the num_buckets guard above).
        from chainermn_tpu.compression import compression_layout
        raw_c = arrays.pop(_COMPRESSION_META_KEY, None)
        saved_c = json.loads(str(raw_c)) if raw_c is not None else None
        live_c = compression_layout(state)
        if saved_c is not None and live_c is None:
            raise ValueError(
                f"checkpoint {where} carries error-feedback compression "
                f"state for {saved_c['specs']} but the resume target has "
                f"no compression configured — rebuild with the same "
                f"compression config (fsdp_init(bucket_compressors=...) "
                f"/ create_multi_node_optimizer(compression=...)), or "
                f"restart training fresh to drop the EF state")
        if saved_c is None and live_c is not None:
            raise ValueError(
                f"checkpoint {where} has no compression state but the "
                f"resume target expects EF state for {live_c['specs']} — "
                f"resume into an uncompressed state and re-init, or save "
                f"from a compressed run; EF residuals cannot be "
                f"fabricated from an uncompressed checkpoint")
        if saved_c is not None and saved_c != live_c:
            raise ValueError(
                f"checkpoint {where} compression config {saved_c} does "
                f"not match the live config {live_c} — the EF residuals "
                f"and delayed scales are bound to the compressor spec; "
                f"pass the identical compression config, or restart "
                f"fresh under the new one")
        # Plan-table pin: a checkpoint saved after an online hot-swap is
        # bound to the swapped table's content hash — resuming without
        # it (or with a different one) would silently execute different
        # plans than the run that saved (ADVICE-r5 posture: fail loudly,
        # name the fix).
        from chainermn_tpu.planner.online import active_plan_table_meta
        raw_t = arrays.pop(_PLAN_TABLE_META_KEY, None)
        saved_t = json.loads(str(raw_t)) if raw_t is not None else None
        live_t = active_plan_table_meta()
        if saved_t is not None and live_t is None:
            raise ValueError(
                f"checkpoint {where} was saved after an online plan-table "
                f"hot-swap (table_hash={saved_t['table_hash']}, swap step "
                f"{saved_t['swap_step']}) but no active plan table is "
                f"registered in this process — reload the swapped table "
                f"(PlanTable.load) and register it with "
                f"planner.online.set_active_plan_table before resuming, "
                f"or, to deliberately discard the tuned plans, clear the "
                f"pin by resuming into a fresh run without the sidecar "
                f"(re-save after planner.online.clear_active_plan_table)")
        if saved_t is not None and \
                saved_t["table_hash"] != live_t["table_hash"]:
            raise ValueError(
                f"checkpoint {where} pins plan table "
                f"{saved_t['table_hash']} (hot-swapped at step "
                f"{saved_t['swap_step']}) but the active table is "
                f"{live_t['table_hash']} — the run would silently execute "
                f"different collective plans than the one that saved; "
                f"register the matching table via "
                f"planner.online.set_active_plan_table(PlanTable.load(...)) "
                f"or re-tune from scratch with "
                f"planner.online.clear_active_plan_table()")
        # Generic leaf-shape validation (also catches a legacy FSDP
        # checkpoint without the sidecar, or a plain checkpoint resumed
        # into an FSDP target): every mismatch beats a cryptic unflatten
        # or a silently mis-sharded device_put.
        n_saved = sum(1 for k in arrays if k.startswith("leaf_"))
        if n_saved != len(leaves):
            raise ValueError(
                f"checkpoint {where} has {n_saved} leaves but the resume "
                f"target has {len(leaves)} — the state structure changed "
                f"(sharded vs unsharded states do not interchange; "
                f"fsdp_full_params is the export path)")
        for i, leaf in enumerate(leaves):
            want = tuple(getattr(leaf, "shape", ()) or ())
            got = tuple(arrays[f"leaf_{i}"].shape)
            if want != got:
                raise ValueError(
                    f"checkpoint {where} leaf_{i} has shape {got} but the "
                    f"resume target expects {want} — likely a world-size "
                    f"or sharding-mode mismatch (see fsdp_full_params for "
                    f"the supported cross-mode export)")

    def resume(self, state):
        """Restore the latest consistent generation into ``state``'s
        structure.  Returns ``(state, iteration)``; ``iteration`` is None
        when nothing could be resumed (fresh start)."""
        from chainermn_tpu.observability import flight_recorder as _flight

        gen = self.latest_consistent_generation()
        if gen is None:
            return state, None
        fr = _flight.get_flight_recorder()
        tok = None
        if fr is not None:
            tok = fr.span_begin("checkpoint", "checkpoint_resume",
                                generation=gen)
        try:
            leaves, treedef = jax.tree.flatten(state)
            with np.load(self._file(gen)) as data:
                arrays = {k: data[k] for k in data.files}
            self._validate_restore(arrays, state, leaves, gen)
            paths = [jax.tree_util.keystr(path) for path, _ in
                     jax.tree_util.tree_flatten_with_path(state)[0]]
            arrays = {f"leaf_{i}": _in_live_dtype(
                arrays[f"leaf_{i}"], leaf, f"leaf_{i} ({paths[i]})")
                for i, leaf in enumerate(leaves)}
            restored = _unflatten_state(arrays, treedef, leaves)
            # preserve shardings of the live state (host-local placement;
            # see _place_like for why this must not cross processes)
            restored = jax.tree.map(_place_like, restored, state)
        finally:
            if tok is not None:
                fr.span_end(tok)
        return restored, gen

    def finalize(self):
        self.comm.barrier()


class _OrbaxCheckpointer:
    """Orbax-backed variant — the TPU-ecosystem checkpoint format.

    Same interface as :class:`_MultiNodeCheckpointer`, delegating
    atomicity, generation GC (``max_to_keep``) and sharded array
    save/restore to ``orbax.checkpoint.CheckpointManager``.  Restore
    places arrays with the LIVE state's shardings (StandardRestore over
    the abstract pytree), so resuming a sharded train state keeps its
    mesh placement without the manual device_put pass the npz path does.
    Multi-controller runs coordinate through orbax's own barriers (it
    expects ``jax.distributed`` to be initialized, which our bootstrap
    does); the control plane is not involved.
    """

    def __init__(self, comm, path: str, name: str, keep: int = 2):
        import orbax.checkpoint as ocp

        self.comm = comm
        self.name = name
        self._ocp = ocp
        # keep=0 -> max_to_keep=None: "retain every generation", matching
        # the npz backend's GC (which skips collection when keep is 0).
        self._mgr = ocp.CheckpointManager(
            os.path.abspath(os.path.join(path, name)),
            options=ocp.CheckpointManagerOptions(
                max_to_keep=keep or None, create=True))

    def save(self, state, iteration: int):
        self._mgr.save(iteration,
                       args=self._ocp.args.StandardSave(state))

    def latest_consistent_generation(self) -> Optional[int]:
        # orbax only publishes fully-committed generations, so "latest
        # present" is already the consistency the npz path negotiates
        return self._mgr.latest_step()

    def resume(self, state):
        gen = self.latest_consistent_generation()
        if gen is None:
            return state, None
        abstract = jax.tree.map(ocp_utils_to_abstract, state)
        restored = self._mgr.restore(
            gen, args=self._ocp.args.StandardRestore(abstract))
        return restored, gen

    def finalize(self):
        self._mgr.wait_until_finished()
        self.comm.barrier()


def ocp_utils_to_abstract(x):
    """Live array -> abstract (shape/dtype/sharding) leaf for restore."""
    if hasattr(x, "sharding") and hasattr(x, "dtype"):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
    return x


def consolidate_fsdp_checkpoint(state, metas):
    """Consolidate every FSDP-sharded sub-state of a (restored) training
    state into its full replicated parameter pytree — the world-size-free
    export the serving weight loader consumes
    (:func:`chainermn_tpu.serving.weights.load_inference_params`).

    ``state`` is a training state tree (dicts/lists/tuples) holding one
    or more :class:`~chainermn_tpu.parallel.fsdp.FsdpState` nodes —
    typically the tree just restored by ``checkpointer.resume`` on the
    *training* world (shard layouts are bound to the world size; resume
    on a mismatched world refuses, naming this path).  ``metas`` is the
    matching :class:`~chainermn_tpu.parallel.fsdp.FsdpMeta` — or a
    sequence of them, one per FsdpState in ``iter_fsdp_states`` order.
    Returns the tree with each FsdpState replaced by its full parameter
    pytree (``fsdp_full_params`` — no collective needed); the optimizer
    inner state and any error-feedback compression state are dropped
    (inference has no use for either).
    """
    from chainermn_tpu.parallel.fsdp import (FsdpMeta, FsdpState,
                                             fsdp_full_params,
                                             iter_fsdp_states)

    metas = [metas] if isinstance(metas, FsdpMeta) else list(metas)
    n_states = sum(1 for _ in iter_fsdp_states(state))
    if n_states != len(metas):
        raise ValueError(
            f"state tree holds {n_states} FsdpState(s) but {len(metas)} "
            f"FsdpMeta(s) were given — pass one meta per sharded "
            f"sub-state, in iter_fsdp_states order")
    it = iter(metas)

    def walk(node):
        if isinstance(node, FsdpState):
            return fsdp_full_params(node, next(it))
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, tuple) and not hasattr(node, "_fields"):
            return tuple(walk(v) for v in node)
        if isinstance(node, list):
            return [walk(v) for v in node]
        return node

    return walk(state)


def create_multi_node_checkpointer(communicator, path: str,
                                   name: str = "snapshot", keep: int = 2,
                                   backend: str = "npz"):
    """Reference signature: ``create_multi_node_checkpointer(name, comm,
    path=...)`` 〔extensions/checkpoint.py〕.  ``backend="npz"`` (default)
    is the self-contained per-rank format; ``backend="orbax"`` delegates
    to the TPU ecosystem's checkpoint library (sharded arrays, async
    commit protocol, same save/resume/GC interface).
    ``backend="async"`` wraps the npz format in the elastic runtime's
    background-persist thread (:class:`chainermn_tpu.elastic.
    AsyncCheckpointer`): ``save`` only pays the device->host snapshot at
    the step boundary and the npz write happens off the critical path
    (``async_ckpt_stall_ms`` in docs/elasticity.md).

    ``keep`` retains the newest *keep* generations in both backends;
    ``keep=0`` disables garbage collection entirely (every generation is
    kept forever — both backends agree on this reading).
    """
    if keep < 0:
        raise ValueError(f"keep must be >= 0 (got {keep}); "
                         f"0 means retain every generation")
    if backend == "orbax":
        return _OrbaxCheckpointer(communicator, path, name, keep)
    if backend == "async":
        from chainermn_tpu.elastic.async_ckpt import AsyncCheckpointer
        return AsyncCheckpointer(
            _MultiNodeCheckpointer(communicator, path, name, keep))
    if backend != "npz":
        raise ValueError(f"unknown checkpoint backend {backend!r} "
                         "(expected 'npz', 'async' or 'orbax')")
    return _MultiNodeCheckpointer(communicator, path, name, keep)
