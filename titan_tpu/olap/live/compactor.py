"""EpochCompactor: fold the delta overlay back into the base CSR.

Compaction is the live plane's epoch boundary: the overlay's live adds
and tombstoned base rows are merged into a fresh dst-sorted snapshot,
the new epoch is republished to the serving pool (running jobs keep
their leased (snapshot, overlay-view) pair; new jobs lease the merged
base with an empty overlay), and only THEN do the device-layout caches
of the old base die — the acceptance contract that a refresh under
writes never evicts or re-uploads the base CSR until the compactor
republishes.

Two merge implementations (ISSUE 9):

* **device** (default) — the next epoch's chunked CSR is computed
  entirely in HBM by ``ops/epoch_merge.merge_chunked_csr`` from the
  base CSR device arrays + the overlay view (both already resident),
  and the host-durable snapshot is synced from delta pages
  (``snapshot.merge_delta`` — O(E) memcpy, no O(E log E) sort, no
  download). Epochs are double-buffered through the HBM ledger: the
  next epoch's CSR bytes are reserved BESIDE the current epoch before
  the merge runs, the merged snapshot is published with its device CSR
  pre-attached (no re-upload), and the old epoch's reservation is
  released by the pool's retire path. Per-epoch H2D cost: zero beyond
  the delta pages the overlay already shipped incrementally.
* **host** — the oracle: filter + concatenate + ``from_arrays``'s full
  stable sort, leaving a snapshot with NO device CSR (the next run
  re-uploads the whole image — charged eagerly to
  ``serving.live.upload_bytes``). This is the fallback whenever the
  device path cannot run, and every fallback is LOUD:
  ``serving.live.device_merge_fallbacks`` counts it and ``stats()``
  records the reason (``GET /live``).

Policy: compact when the overlay's add-buffer fill or its tombstone
fraction crosses budget (defaults 0.5 / 0.05 — configurable per plane
since ISSUE 9, no longer module-constant-only), when a delta cannot be
expressed in the overlay at all (vertex-set changes, edges to unknown
vertices — the general ``apply_changes`` path handles those on the
merged snapshot), or when the HBM ledger refuses an overlay growth.
"""

from __future__ import annotations

import time

import numpy as np

#: default thresholds — fill is fraction of the CURRENT capacity bucket
#: (so small overlays compact before jumping buckets), tombstones are a
#: fraction of base edge rows (dead slots cost gather bandwidth every
#: round until compacted)
MAX_FILL = 0.5
MAX_TOMB_FRACTION = 0.05


class EpochCompactor:
    """Merge policy + merge implementation. Mode/fallback telemetry is
    instance state (one compactor per plane); byte/fallback counters go
    through the ``metrics`` manager the plane passes per call."""

    def __init__(self, max_fill: float = MAX_FILL,
                 max_tomb_fraction: float = MAX_TOMB_FRACTION,
                 *, device_merge: bool = True,
                 verify_device: bool = False):
        self.max_fill = float(max_fill)
        self.max_tomb_fraction = float(max_tomb_fraction)
        self.device_merge = bool(device_merge)
        # paranoia knob: download the device-merged dstT (D2H charged
        # to serving.live.download_bytes) and compare it to the
        # host-synced mirror; a mismatch degrades to the host oracle
        self.verify_device = bool(verify_device)
        self.device_merges = 0
        self.host_merges = 0
        self.last_mode: str = "none"
        self.fallbacks: dict = {}      # reason -> count

    def policy(self) -> dict:
        """The active policy + merge-mode telemetry — surfaced by
        ``LiveGraphPlane.stats()`` under ``GET /live``."""
        return {"max_fill": self.max_fill,
                "max_tomb_fraction": self.max_tomb_fraction,
                "device_merge": self.device_merge,
                "verify_device": self.verify_device,
                "merge_mode": self.last_mode,
                "device_merges": self.device_merges,
                "host_merges": self.host_merges,
                "fallbacks": dict(self.fallbacks)}

    def should_compact(self, overlay) -> bool:
        if overlay.count == 0 and overlay.tomb_count == 0:
            return False
        return (overlay.fill_fraction() >= self.max_fill
                or overlay.tombstone_fraction() >= self.max_tomb_fraction)

    # -- host oracle ---------------------------------------------------------

    def merge(self, snapshot, overlay):
        """Base + overlay → a fresh snapshot over the SAME vertex set
        (vertex-set changes ride the subsequent ``apply_changes`` call
        on the merged object). Pure host-array work — the full stable
        re-sort; the old snapshot's arrays are left untouched for jobs
        still leasing them. This is the ORACLE the device path is
        pinned bit-equal to (tests/test_live_compact_device.py) and the
        fallback it degrades to."""
        from titan_tpu.olap.tpu import snapshot as snap_mod

        keep = ~overlay.tomb_row_mask
        src = snapshot.src[keep]
        dst = snapshot.dst[keep]
        labs = snapshot.labels[keep] if snapshot.labels is not None \
            else None
        a_src, a_dst, a_lab = overlay.live_adds()
        if len(a_src):
            src = np.concatenate([src, a_src])
            dst = np.concatenate([dst, a_dst])
            if labs is not None:
                labs = np.concatenate([labs, a_lab])
        merged = snap_mod.from_arrays(
            snapshot.n, src, dst, snapshot.vertex_ids,
            labels=labs, label_names=snapshot.label_names)
        return self._carry_over(snapshot, merged)

    @staticmethod
    def _carry_over(snapshot, merged):
        # dense vertex-property columns stay aligned (same vertex set);
        # carry them over so compiled has()/values() keep working
        merged.vertex_values = dict(snapshot.vertex_values)
        merged._build_params = dict(snapshot._build_params or {})
        merged.epoch = snapshot.epoch
        return merged

    # -- device path ---------------------------------------------------------

    def _fallback(self, reason: str, metrics) -> None:
        self.fallbacks[reason] = self.fallbacks.get(reason, 0) + 1
        if metrics is not None:
            metrics.counter(
                "serving.live.device_merge_fallbacks").inc()

    def compact(self, snapshot, overlay, *, ledger=None, metrics=None,
                host_only: bool = False, on_resident=None):
        """One epoch boundary: returns ``(merged_snapshot, mode)`` with
        ``mode in ("device", "host")``.

        Device path preconditions — any miss degrades LOUDLY to the
        host oracle (fallback reason recorded, counter bumped):

        * ``host_only`` is False (vertex-set changes take the general
          ``apply_changes`` path, which invalidates device caches — a
          device merge would be wasted work);
        * the base chunked CSR is device-resident (otherwise there is
          nothing in HBM to merge against and the host path is
          strictly cheaper) and non-empty;
        * int32 slot ids can express both layouts;
        * the HBM ledger (when attached) can hold the NEXT epoch's CSR
          beside the current one — the double-buffer reservation.

        ``on_resident(merged)`` (when given) registers the published
        snapshot with the ledger owner's eviction map so a later
        eviction of the unpinned epoch actually drops its device CSR.
        """
        from titan_tpu.ops import epoch_merge

        if host_only:
            return self._host("apply-path", snapshot, overlay,
                              metrics)
        if not self.device_merge:
            return self._host(None, snapshot, overlay, metrics)
        csr = getattr(snapshot, "_hybrid_csr", None)
        if csr is None:
            return self._host("base-not-resident", snapshot,
                              overlay, metrics)
        if snapshot.num_edges == 0:
            return self._host("empty-base", snapshot, overlay,
                              metrics)
        deg, degc, colstart, q_new = \
            epoch_merge.merged_degrees_host(snapshot, overlay)
        if not (epoch_merge.fits_int32(int(csr["q_total"]))
                and epoch_merge.fits_int32(q_new)):
            return self._host("int32-overflow", snapshot,
                              overlay, metrics)
        reserve_key = None
        nbytes = 0
        if ledger is not None:
            from titan_tpu.olap.serving.hbm import (AdmissionError,
                                                    chunked_csr_bytes)
            nbytes = chunked_csr_bytes(snapshot.n, q_new)
            reserve_key = ("live-epoch-next", id(self))
            try:
                # the double-buffer: next epoch's CSR beside the
                # current one. AdmissionError = the ledger cannot hold
                # two epochs → loud host degrade.
                ledger.reserve(reserve_key, nbytes)
            except AdmissionError:
                return self._host("ledger-full", snapshot,
                                  overlay, metrics)
        try:
            return self._device(snapshot, overlay, csr, deg, degc,
                                colstart, q_new, ledger, reserve_key,
                                nbytes, metrics, on_resident)
        except Exception as e:
            # ANY kernel failure degrades to the host oracle — not
            # just the int32/layout ValueErrors the CPU path can hit:
            # on real hardware the merge can die with an
            # XlaRuntimeError (HBM allocator RESOURCE_EXHAUSTED under
            # fragmentation the ledger model didn't predict), and
            # letting it escape would leak the pinned double-buffer
            # reservation and skip the epoch entirely
            if ledger is not None:
                ledger.release(reserve_key)
            return self._host(f"kernel: {type(e).__name__}: {e}",
                              snapshot, overlay, metrics)

    def _device(self, snapshot, overlay, csr, deg, degc, colstart,
                q_new, ledger, reserve_key, nbytes, metrics,
                on_resident):
        import jax

        from titan_tpu.olap.tpu import snapshot as snap_mod
        from titan_tpu.ops import epoch_merge

        view = overlay.view()
        t0 = time.time()
        out = epoch_merge.merge_chunked_csr(
            csr, view, q_total_new=q_new, e_base=snapshot.num_edges)
        jax.block_until_ready(out["dstT"])
        device_ms = (time.time() - t0) * 1e3
        # host-durable sync from delta pages: drop tombstoned rows,
        # insert the adds — O(E) memcpy + O(delta log delta), never the
        # full re-sort, never a device download
        a_src, a_dst, a_lab = overlay.live_adds()
        merged = self._carry_over(snapshot, snap_mod.merge_delta(
            snapshot, ~overlay.tomb_row_mask, a_src, a_dst, a_lab))
        out["_host"] = epoch_merge.LazyHostMirror(
            merged, colstart, degc)
        if self.verify_device:
            # D2H readback (charged) + bit-compare vs the host mirror
            got = np.asarray(out["dstT"])
            if metrics is not None:
                metrics.counter("serving.live.download_bytes").inc(
                    got.nbytes)
            if not (got == out["_host"]["dstT"]).all():
                if ledger is not None:
                    ledger.release(reserve_key)
                return self._host("verify-mismatch", snapshot,
                                  overlay, metrics)
        merged._hybrid_csr = out
        if ledger is not None:
            # re-key the double-buffer reservation onto the published
            # snapshot's identity: the scheduler's per-run reserve()
            # pins this same entry, and the pool's retire path releases
            # it — exactly the lifecycle of an uploaded image. Resident
            # but unpinned (the warm-cache state) until a job runs.
            from titan_tpu.olap.serving.hbm import AdmissionError
            ledger.release(reserve_key)
            try:
                ledger.reserve(id(merged), nbytes)
                ledger.unpin(id(merged))
            except AdmissionError:
                pass   # accounting catches up on the next job's reserve
        if on_resident is not None:
            on_resident(merged)
        if metrics is not None:
            metrics.histogram(
                "serving.live.compact_device_ms").update(device_ms)
        self.device_merges += 1
        self.last_mode = "device"
        return merged, "device"

    def _host(self, fallback_reason, snapshot, overlay, metrics):
        if fallback_reason is not None:
            self._fallback(fallback_reason, metrics)
        merged = self.merge(snapshot, overlay)
        if metrics is not None:
            # the host path leaves no device CSR: the next run
            # re-uploads the whole image — charge the epoch for it so
            # upload_bytes reflects what the boundary commits over
            # the host→device link either way
            from titan_tpu.olap.serving.hbm import (price,
                                                    snapshot_csr_bytes)
            price(merged, ("out",), metrics)
            metrics.counter("serving.live.upload_bytes").inc(
                snapshot_csr_bytes(merged))
        self.host_merges += 1
        self.last_mode = "host"
        return merged, "host"
