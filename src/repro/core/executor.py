"""Executor tier: batched block processing behind a backend protocol.

An :class:`ExecutorBackend` consumes the lanes pulled by the scheduler
and performs the paper's apply/propagation step (Alg. 1 lines 5-8) as a
vertex->edge expansion followed by a commutative scatter-combine. Two
backends produce *identical* ``(new_key, edges_scanned,
vertices_processed)`` results:

  * :class:`GatherExecutor` — the reference searchsorted/gather
    expansion: each lane's active edges are enumerated compactly and
    gathered from the global edge array (XLA-native, the engine's
    original inner loop).
  * :class:`PallasExecutor` — drives the TPU-native
    ``frontier_relax`` Pallas kernel: the expansion runs as a one-hot
    membership matmul in VMEM over each lane's contiguous edge window;
    the scatter-combine stays outside the kernel (TPU has no efficient
    arbitrary scatter). Messages round-trip through f32 inside the
    kernel, exact for integer keys below 2**24 (graphs past 16M
    vertices should prefer the gather backend for int-keyed
    algorithms).

Both share the lane-window setup and the scatter-combine epilogue, so
parity is structural: they differ only in how the per-edge ``(dst,
value, valid)`` triples are materialized.

**Bucketed tiling** (``EngineConfig.bucketing``): real graphs are
skewed, so padding every lane to the *global* maxima ``(Vm, We, EK)``
makes one hub block inflate every tick's expansion, scatter, and VMEM
window. The engine partitions scheduling blocks into power-of-two size
classes by vertex count and edge mass (:class:`Tile` per class,
``b_bucket`` block -> class table); :meth:`ExecutorBackend.execute`
routes each pulled lane through ``lax.switch`` to its own class, so the
work *executed* per tick is the sum of the pulled blocks' tile sizes —
not ``lanes x`` the worst block in the graph. Lanes run in lane-major
order through the shared scatter-combine epilogue, which is exactly the
single global tile's flat scatter order, so results (including
floating-point ``add`` state) are bit-identical to the ``bucketing=0``
compat default.

**The Q axis** (concurrent query plane, PR 5): executors are written
against ONE query's `[V]` state and the per-query batch plane maps the
whole tick — executor included — over the batch's leading Q axis
(`lax.map`, i.e. scan). Each query's pass is therefore the solo
computation verbatim: per-lane bucket routing and tile sizes are
unchanged, the scatter order per query is the solo order (bit-parity by
construction), and the pallas kernel needs no vmap batching rule.

**Aggregated mode** (PR 6): for schedule-independent algorithms the
engine's aggregated plane pulls ONE merged worklist and calls
:meth:`ExecutorBackend.execute_many`, which `jax.vmap`s the solo
execute over the Q-stacked `(state, front)` with the lane selection
held fixed. The block windows, bucket routing, and edge indices are
computed once per pulled block and the expansion/scatter vectorize
over a `[Q, ...]` axis — one executor pass per block serving all Q
queries, instead of Q sequential passes. Both backends get this for
free (`lax.switch` keeps its unbatched lane index; the pallas kernel
batches under vmap, interpreted or compiled).

New backends register via :data:`EXECUTORS`.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.api import Algorithm
from repro.core.prefix import cumsum
from repro.kernels.ops import frontier_relax


#: largest ``Vm * We`` tile whose gather expansion finds each slot's
#: owner by comparing it with every vertex (one bool per pair)
COMPARE_ALL_MAX = 1 << 22


@dataclasses.dataclass(frozen=True)
class Tile:
    """Static executor tile sizes for one block size class."""
    Vm: int                   # max vertices per member block
    We: int                   # max total active edges per member (gather)
    EK: int                   # max edge-window span per member (pallas)


def _static():
    return dataclasses.field(metadata=dict(static=True))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ExecTables:
    """Read-only engine tables of one graph, built once per graph.

    A pytree: the arrays are its leaves and ``V`` / ``max_run`` /
    ``tiles`` its static part, so the engine passes the tables to every
    compiled program as an argument — the graph is data the program
    reads, not a constant compiled into it. The executor, the scheduler
    and the buffer pool read their arrays from here.
    """
    all_edges: jnp.ndarray    # [edge slots + tail_pad] int32 destinations
    v_start: jnp.ndarray      # [V] per-vertex edge-array start
    v_deg: jnp.ndarray        # [V] per-vertex degree
    v_sched: jnp.ndarray      # [V] owning scheduling block (sorted)
    is_real: jnp.ndarray      # [V] False for virtual vertices
    sched_first: jnp.ndarray  # [B+1] vertex-id range per scheduling block
    sched_io: jnp.ndarray     # [B] I/O cost in 4 KB slots
    b_bucket: jnp.ndarray     # [B] int32 block -> size class
    b_fill: jnp.ndarray       # [B] static block fill (vertices + edges)
    b_edges: jnp.ndarray      # [B] edge mass (compute cost model)
    vs_first: jnp.ndarray     # [B+1] first index of each block in v_sched
    vs_nonempty: jnp.ndarray  # [B] block owns at least one vertex
    run_left: jnp.ndarray     # [V] indices left in this block's run
    V: int = _static()        # number of vertices (incl. virtual)
    max_run: int = _static()  # most vertices of any scheduling block
    tail_pad: int = _static()  # copies of the last edge slot at the end
    tiles: tuple[Tile, ...] = _static()  # one tile per occupied size class


def edge_window(t: ExecTables, first, width: int):
    """The ``width`` edge slots from the edge start of vertex ``first``
    (a scalar): one contiguous read, not a gather. ``all_edges`` ends in
    ``tail_pad`` copies of its last slot, so a window running past the
    end reads what clipping each index would."""
    base = t.v_start[jnp.minimum(first, t.V - 1)]
    return jax.lax.dynamic_slice(t.all_edges, (base,), (width,))


@dataclasses.dataclass
class ExecResult:
    state: dict               # algorithm state after scatter + on_process
    processed: jnp.ndarray    # bool[V] sources consumed this tick
    activated: jnp.ndarray    # bool[V] vertices whose key improved
    edges_scanned: jnp.ndarray      # i32 scalar
    vertices_processed: jnp.ndarray  # i32 scalar


class ExecutorBackend:
    """Protocol: subclasses implement :meth:`_expand`."""

    name = "base"

    def __init__(self, tables: ExecTables):
        self.t = tables

    # ---- shared lane-window setup ------------------------------------
    def _lane_windows(self, front, eidx, lane_valid, tile: Tile):
        t = self.t
        i32 = jnp.int32
        first = t.sched_first[eidx]
        end = t.sched_first[eidx + 1]
        vids = first[..., None] + jnp.arange(tile.Vm, dtype=i32)
        inrange = vids < end[..., None]
        vids_c = jnp.minimum(vids, t.V - 1)
        vmask = (inrange & lane_valid[..., None] & front[vids_c]
                 & t.is_real[vids_c])
        degs = jnp.where(vmask, t.v_deg[vids_c], 0)
        return first, vids_c, vmask, degs

    # ---- backend-specific expansion ----------------------------------
    def _expand(self, algo: Algorithm, first, vids_c, vmask, degs, msgs,
                key_dtype, tile: Tile):
        """-> (dstf, val, svalid): per-slot destination (V = sentinel),
        candidate value, and validity mask, any [lanes, W] layout."""
        raise NotImplementedError

    def _combine(self, algo, ext, dstf, val, svalid):
        if algo.combine == "min":
            return ext.at[dstf.ravel()].min(val.ravel())
        return ext.at[dstf.ravel()].add(
            jnp.where(svalid, val, 0).ravel())

    # ---- the full apply / propagation step ---------------------------
    def execute(self, algo: Algorithm, state, front, eidx,
                lane_valid) -> ExecResult:
        if len(self.t.tiles) == 1:
            return self._execute_batched(algo, state, front, eidx,
                                         lane_valid, self.t.tiles[0])
        return self._execute_bucketed(algo, state, front, eidx,
                                      lane_valid)

    def _execute_batched(self, algo, state, front, eidx, lane_valid,
                         tile) -> ExecResult:
        """Single global tile: all lanes expand as one batch."""
        t = self.t
        first, vids_c, vmask, degs = self._lane_windows(front, eidx,
                                                        lane_valid, tile)
        msgs = algo.apply(state, vids_c, vmask, degs)

        processed = jnp.zeros(t.V, bool).at[vids_c.ravel()].max(
            vmask.ravel())
        if algo.on_process is not None:
            state = algo.on_process(state, processed)
        old_key = state[algo.key]

        dstf, val, svalid = self._expand(algo, first, vids_c, vmask, degs,
                                         msgs, old_key.dtype, tile)
        ext = jnp.concatenate([old_key,
                               algo.neutral(old_key.dtype)[None]])
        ext = self._combine(algo, ext, dstf, val, svalid)
        new_key = ext[:t.V]
        activated = algo.activated(old_key, new_key, t.v_deg) & t.is_real
        state = dict(state)
        state[algo.key] = new_key
        return ExecResult(
            state=state, processed=processed, activated=activated,
            edges_scanned=jnp.sum(degs).astype(jnp.int32),
            vertices_processed=jnp.sum(vmask).astype(jnp.int32))

    def execute_many(self, algo: Algorithm, states, fronts, eidx,
                     lane_valid) -> ExecResult:
        """Aggregated batch mode: expand each pulled block ONCE against
        the Q-stacked state.

        ``states`` / ``fronts`` carry a leading Q axis; ``eidx`` /
        ``lane_valid`` are the merged worklist's single lane selection,
        shared by every query. The solo :meth:`execute` is ``jax.vmap``d
        over the stacked axis, so lane windows, bucket routing
        (``lax.switch`` on the unbatched lane index), and edge gathers
        are computed once per pulled block while apply/expand/scatter
        vectorize over ``[Q, ...]``. Returns an :class:`ExecResult`
        whose fields all carry the leading Q axis (``edges_scanned`` /
        ``vertices_processed`` become per-query ``i32[Q]`` — frontier
        masks differ per query even under the shared pull order).
        """

        def one(state, front):
            r = self.execute(algo, state, front, eidx, lane_valid)
            return (r.state, r.processed, r.activated, r.edges_scanned,
                    r.vertices_processed)

        state, processed, activated, nedges, nverts = jax.vmap(one)(
            states, fronts)
        return ExecResult(state=state, processed=processed,
                          activated=activated, edges_scanned=nedges,
                          vertices_processed=nverts)

    def _execute_bucketed(self, algo, state, front, eidx,
                          lane_valid) -> ExecResult:
        """Per-lane ``lax.switch`` routing: each lane runs its block's
        own size-class expansion, so executed work (expansion AND
        scatter updates) is proportional to the blocks actually pulled.
        Lane-major accumulation reproduces the batched path's flat
        scatter order bit-for-bit.

        Algorithms without ``on_process`` fuse window/scatter into one
        pass per lane; with it (PPR residual consumption), a first pass
        combines the processed mask before the state mutation, exactly
        as in the batched path.
        """
        t = self.t
        i32 = jnp.int32
        E = eidx.shape[0]
        lane_bucket = t.b_bucket[eidx]
        cheapest = min(range(len(t.tiles)),
                       key=lambda k: (t.tiles[k].Vm + t.tiles[k].We
                                      + t.tiles[k].EK))
        lane_k = jnp.where(lane_valid, lane_bucket, cheapest)
        state_pre = state

        # _lane_windows broadcasts over [..., None], so a scalar
        # (eidx, lane_valid) pair yields this one lane's 1-D window —
        # the same masking code as the batched path, not a copy

        def mark_branch(tile):
            def br(op):
                processed, nedges, nverts, e, valid = op
                _, vc, vmask, degs = self._lane_windows(front, e, valid,
                                                        tile)
                return (processed.at[vc].max(vmask),
                        nedges + jnp.sum(degs).astype(i32),
                        nverts + jnp.sum(vmask).astype(i32), e, valid)
            return br

        def scatter_branch(tile, key_dtype, fused):
            def br(op):
                ext, processed, nedges, nverts, e, valid = op
                first, vc, vmask, degs = self._lane_windows(front, e,
                                                            valid, tile)
                msgs = algo.apply(state_pre, vc[None], vmask[None],
                                  degs[None])
                dstf, val, svalid = self._expand(
                    algo, first[None], vc[None], vmask[None], degs[None],
                    msgs, key_dtype, tile)
                ext = self._combine(algo, ext, dstf, val, svalid)
                if fused:
                    processed = processed.at[vc].max(vmask)
                    nedges = nedges + jnp.sum(degs).astype(i32)
                    nverts = nverts + jnp.sum(vmask).astype(i32)
                return ext, processed, nedges, nverts, e, valid
            return br

        def run_lanes(branches, op_rest):
            # a loop, not E unrolled copies of every branch: same lane
            # order, a program a quarter the size at 4 lanes
            def lane(i, op_rest):
                op = tuple(op_rest) + (eidx[i], lane_valid[i])
                if len(branches) == 1:
                    out = branches[0](op)
                else:
                    out = jax.lax.switch(lane_k[i], branches, op)
                return tuple(out[:-2])
            return jax.lax.fori_loop(0, E, lane, tuple(op_rest))

        processed = jnp.zeros(t.V, bool)
        nedges = jnp.zeros((), i32)
        nverts = jnp.zeros((), i32)
        fused = algo.on_process is None
        if not fused:
            processed, nedges, nverts = run_lanes(
                [mark_branch(tl) for tl in t.tiles],
                (processed, nedges, nverts))
            state = algo.on_process(state, processed)
        old_key = state[algo.key]
        ext = jnp.concatenate([old_key,
                               algo.neutral(old_key.dtype)[None]])
        ext, processed, nedges, nverts = run_lanes(
            [scatter_branch(tl, old_key.dtype, fused) for tl in t.tiles],
            (ext, processed, nedges, nverts))
        new_key = ext[:t.V]
        activated = algo.activated(old_key, new_key, t.v_deg) & t.is_real
        state = dict(state)
        state[algo.key] = new_key
        return ExecResult(
            state=state, processed=processed, activated=activated,
            edges_scanned=nedges, vertices_processed=nverts)


class GatherExecutor(ExecutorBackend):
    """Compact active-edge enumeration via searchsorted + global gather."""

    name = "gather"

    def _expand(self, algo, first, vids_c, vmask, degs, msgs, key_dtype,
                tile):
        t = self.t
        i32 = jnp.int32
        cum_e = cumsum(degs, axis=1)
        tot = cum_e[:, -1]
        slots = jnp.arange(tile.We, dtype=i32)
        # comparing every slot with every vertex is a few vector passes
        # where a binary search is log2(Vm) serial gathers; the search
        # stays for tiles too big to compare whole
        method = "compare_all" if tile.Vm * tile.We <= COMPARE_ALL_MAX \
            else "scan"
        owner = jax.vmap(lambda ce: jnp.searchsorted(
            ce, slots, side="right", method=method))(cum_e)
        owner_c = jnp.minimum(owner, tile.Vm - 1).astype(i32)
        prev = cum_e - degs
        within_e = slots[None, :] - jnp.take_along_axis(prev, owner_c,
                                                        axis=1)
        svalid = slots[None, :] < tot[:, None]
        starts_lane = t.v_start[vids_c]
        gidx = jnp.take_along_axis(starts_lane, owner_c, axis=1) + within_e
        gidx = jnp.where(svalid, gidx, 0)
        dst = t.all_edges[gidx]
        msg_e = jnp.take_along_axis(msgs, owner_c, axis=1)
        val = algo.edge_value(msg_e)
        dstf = jnp.where(svalid, dst, t.V)
        return dstf, val, svalid


class PallasExecutor(ExecutorBackend):
    """Lane-batched ``frontier_relax`` kernel over contiguous edge windows.

    Each lane's scheduling block owns a contiguous range of edge slots
    starting at its first vertex's edge start; the kernel expands
    messages onto those slots via an MXU membership matmul. Values are
    cast back to the key dtype and ``edge_value`` is applied outside the
    kernel, so algorithm semantics match the gather backend exactly.
    Under bucketed tiling each lane invokes the kernel with its own size
    class's ``(Vm_k, EK_k)`` tile, so hub blocks no longer size every
    lane's VMEM window.
    """

    name = "pallas"

    def _expand(self, algo, first, vids_c, vmask, degs, msgs, key_dtype,
                tile):
        t = self.t
        i32 = jnp.int32
        if jnp.issubdtype(key_dtype, jnp.integer) and t.V >= 2 ** 24:
            raise ValueError(
                "pallas executor round-trips messages through f32, which "
                f"is exact only below 2**24; V={t.V} integer keys would "
                "be silently corrupted — use executor='gather'")
        base = t.v_start[jnp.minimum(first, t.V - 1)]
        starts_local = jnp.where(vmask, t.v_start[vids_c] - base[:, None],
                                 0).astype(i32)
        edges_lane = jax.vmap(lambda f: edge_window(t, f, tile.EK))(first)
        vals, valid = frontier_relax(
            starts_local, degs.astype(i32), vmask.astype(i32),
            msgs.astype(jnp.float32), edges_lane, op="identity")
        msg_slot = jnp.where(valid, vals, 0).astype(key_dtype)
        val = algo.edge_value(msg_slot)
        dstf = jnp.where(valid, edges_lane, t.V)
        return dstf, val, valid


EXECUTORS: dict[str, type[ExecutorBackend]] = {
    e.name: e for e in (GatherExecutor, PallasExecutor)
}


def make_executor(name: str, tables: ExecTables) -> ExecutorBackend:
    try:
        return EXECUTORS[name](tables)
    except KeyError:
        raise ValueError(
            f"unknown executor {name!r}; "
            f"available: {sorted(EXECUTORS)}") from None
