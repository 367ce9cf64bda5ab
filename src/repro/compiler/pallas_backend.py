"""Pallas emission backend: fused region lowering.

Where :mod:`.lowering` schedules the transformed graph node by node (every
Reader/Writer a flat HBM gather/scatter, every adapter a value-identity
loop), this backend partitions the graph into **fused compute regions** —
the maximal ``Memory → Reader → … → Writer → Memory`` chains between memory
containers, with Sync boundaries realized by the Pallas pipeline itself —
and emits each region as *one* blocked kernel.  The paper's pump factor M is
realized structurally: as the **innermost temporal grid axis** of the
region's grid, not as an in-kernel loop.

    Mode T: the innermost grid dimension (extent G) splits into G/M wide
            transactions × M narrow beats — offsets rewritten by the exact
            substitution ``g -> g*M + _pump``.
    Mode R: the output-carrying block dimension narrows by M and the ``_pump``
            axis walks its M sub-tiles; operand blocks narrowed only where
            they share the output's grid symbol.

Each region is emitted at the highest tier its structure admits:

``pallas``     a real ``pl.pallas_call``: every access has a *block-unit*
               index map (offsets divide by the block), every compute a
               per-tile body (``meta['tile_fn']``), and the output tiling
               covers the memory.  Used on TPU; on CPU only when forced
               (``pallas_mode='interpret'``), since interpret mode exists
               for validation, not speed.
``blockloop``  a structurally identical fused ``fori_loop`` over the same
               grid with element-unit ``dynamic_slice`` blocks — the
               ``jax.jit`` fallback of the pallas emission on CPU.  Handles
               overlapping halo windows pallas block indexing cannot.
``gather``     region-level fallback: one gather → compute-chain → scatter
               per region (still fused; no per-node barriers or gearbox
               loops).  Used when computes lack a tile form (e.g. the
               dependency-carrying floyd-warshall pivot loop).

Grid dimensions absent from the output access (plus the temporal axis when
it splits one of them) are *reduction* dimensions: the emitted kernel
zero-initializes the output tile on their first visit and accumulates with
``+`` thereafter — computes marked ``meta['reduce']='add'`` return partial
contributions per grid step.

Sequential-carry regions (a compute with ``meta['carry']``, e.g. flash
attention's online softmax or the SSD inter-chunk state) get a *carry-aware*
emission: the carry axis stays the innermost sequential grid dimension, the
loop-carried state threads through the fused loop (``blockloop`` carries it
in the ``fori_loop`` state; ``pallas`` keeps it in VMEM scratch with
``pl.when`` init/finalize — exactly the hand-written flash-attention
schedule, now derived), and the region may write *multiple* output memories
(the attention tile plus its running max/denominator).  Mode T splits the
carry axis into wide transactions × M dependent beats; mode R narrows the
block dimensions labelled by the compute's ``meta['axes']`` correspondence
and runs each sub-tile through its own full sweep.
"""
from __future__ import annotations

import dataclasses
import functools
import re
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.executor import _toposort
from repro.core.ir import CarrySpec, Graph, NodeKind
from repro.core.pump_plan import VMEM_BYTES
from repro.core.symbolic import (Affine, BlockedAccess, blocked_access,
                                 narrow_block, split_temporal)
from repro.testing import faults

from .lowering import (LoweringError, _indices, carry_sequence_apply,
                       scatter_indices)

PUMP_SYM = "_pump"
_PASS_THROUGH = (NodeKind.STREAM, NodeKind.SYNC, NodeKind.ISSUER,
                 NodeKind.PACKER, NodeKind.READER, NodeKind.WRITER)


# ------------------------------------------------------------ region graph --
@dataclasses.dataclass
class Region:
    """One fused region: the modules between memory containers."""

    name: str
    members: List[str]                       # non-memory node names
    computes: List[str]                      # topo order
    # per compute, operand sources in edge order:
    #   ("mem", memory name, AccessPattern) | ("comp", upstream compute name)
    bindings: Dict[str, List[Tuple]]
    # (compute, memory, AccessPattern) writes out of the region
    outputs: List[Tuple[str, str, Any]]
    pump: int = 1
    mode: str = "T"


def _trace_to_source(g: Graph, edge) -> Tuple:
    """Walk an in-edge backwards through pass-through modules to its origin:
    a memory (with the reader's access pattern) or an upstream compute."""
    e = edge
    while True:
        src = g.nodes[e.src]
        if src.kind == NodeKind.MEMORY:
            return ("mem", src.name, e.access)
        if src.kind == NodeKind.COMPUTE:
            return ("comp", src.name)
        ins = g.in_edges(src.name)
        if len(ins) != 1:
            raise LoweringError(
                f"pass-through module {src.name} has {len(ins)} inputs")
        e = ins[0]


def _trace_to_sink(g: Graph, edge) -> Optional[Tuple]:
    """Walk an out-edge forward to a memory write; None when it feeds a
    downstream compute inside the region instead."""
    e = edge
    while True:
        dst = g.nodes[e.dst]
        if dst.kind == NodeKind.MEMORY:
            return (dst.name, e.access)
        if dst.kind == NodeKind.COMPUTE:
            return None
        outs = g.out_edges(dst.name)
        if len(outs) != 1:
            raise LoweringError(
                f"pass-through module {dst.name} has {len(outs)} outputs")
        e = outs[0]


def partition_regions(g: Graph) -> List[Region]:
    """Split ``g`` into fused regions: connected components of the module/
    stream subgraph, with memory containers as the region boundaries."""
    # union-find over non-memory nodes
    parent: Dict[str, str] = {n.name: n.name for n in g.nodes.values()
                              if n.kind != NodeKind.MEMORY}

    def root(n: str) -> str:
        while parent[n] != n:
            parent[n] = parent[parent[n]]
            n = parent[n]
        return n

    for e in g.edges:
        if e.src in parent and e.dst in parent:
            parent[root(e.src)] = root(e.dst)

    groups: Dict[str, List[str]] = {}
    for n in parent:
        groups.setdefault(root(n), []).append(n)

    order = _toposort(g)
    pos = {n: i for i, n in enumerate(order)}
    regions = []
    for members in groups.values():
        members.sort(key=pos.__getitem__)
        computes = [n for n in members
                    if g.nodes[n].kind == NodeKind.COMPUTE]
        if not computes:
            continue   # dangling adapters with no compute: nothing to emit
        bindings: Dict[str, List[Tuple]] = {}
        outputs: List[Tuple[str, str, Any]] = []
        for c in computes:
            bindings[c] = [_trace_to_source(g, e) for e in g.in_edges(c)]
            for e in g.out_edges(c):
                sink = _trace_to_sink(g, e)
                if sink is not None:
                    outputs.append((c, sink[0], sink[1]))
        pump = max((g.nodes[c].pump for c in computes), default=1)
        mode = next((g.nodes[c].meta.get("pump_mode") for c in computes
                     if g.nodes[c].meta.get("pump_mode")), "T")
        regions.append(Region(name=computes[0], members=members,
                              computes=computes, bindings=bindings,
                              outputs=outputs, pump=pump, mode=mode))

    # schedule regions by memory dataflow, not by node position: a region
    # reading memory m must run after every region writing m (the node-level
    # toposort guarantees this order exists)
    writers: Dict[str, List[int]] = {}
    for i, r in enumerate(regions):
        for _c, mem, _a in r.outputs:
            writers.setdefault(mem, []).append(i)
    deps: Dict[int, set] = {i: set() for i in range(len(regions))}
    for i, r in enumerate(regions):
        for srcs in r.bindings.values():
            for src in srcs:
                if src[0] == "mem":
                    deps[i].update(j for j in writers.get(src[1], ())
                                   if j != i)
    ordered: List[Region] = []
    done: set = set()
    while len(done) < len(regions):
        ready = sorted(
            (i for i in deps if i not in done and deps[i] <= done),
            key=lambda i: pos[regions[i].computes[0]])
        if not ready:   # pragma: no cover - node toposort forbids cycles
            raise LoweringError("cyclic memory dependency between regions")
        for i in ready:
            done.add(i)
            ordered.append(regions[i])
    return ordered


# ------------------------------------------------------------- region plan --
@dataclasses.dataclass
class RegionPlan:
    """A tile-emittable region: unified grid + blocked views per operand."""

    region: Region
    grid: Tuple[Tuple[str, int], ...]        # outermost → innermost
    reduce_syms: Tuple[str, ...]             # grid syms absent from output
    blocks: Dict[Tuple[str, int], BlockedAccess]   # (compute, operand idx)
    # (compute, memory, blocked view) per region output, primary first
    outputs: List[Tuple[str, str, BlockedAccess]]
    tile_fns: Dict[str, Callable]
    pump: int = 1                            # realized temporal factor
    mode: str = "T"
    pallas_ok: bool = True                   # block-unit maps + full coverage
    # sequential-carry emission (single-compute regions only)
    carry: Optional[CarrySpec] = None
    carry_syms: Tuple[str, ...] = ()         # carry axis (+ mode-T _pump)
    carry_narrow: Dict[int, Tuple[int, int]] = \
        dataclasses.field(default_factory=dict)   # state idx -> (dim, M)
    outer_syms: Tuple[str, ...] = ()         # step syms excluding the axis

    # single-output convenience views (primary output)
    @property
    def out_compute(self) -> str:
        return self.outputs[0][0]

    @property
    def out_mem(self) -> str:
        return self.outputs[0][1]

    @property
    def out_block(self) -> BlockedAccess:
        return self.outputs[0][2]


def _tile_fn_of(g: Graph, name: str) -> Optional[Callable]:
    n = g.nodes[name]
    fn = n.meta.get("tile_fn")
    if fn is None and n.meta.get("elementwise"):
        fn = n.fn
    return fn


def plan_region(g: Graph, region: Region,
                warn: Callable[[str], None]) -> Optional[RegionPlan]:
    """Derive the blocked emission plan for a region, or None when the
    region must fall back to gather emission (reason passed to ``warn``)."""
    carry: Optional[CarrySpec] = None
    if len(region.computes) == 1:
        carry = g.nodes[region.computes[0]].meta.get("carry")
    elif any(g.nodes[c].meta.get("carry") for c in region.computes):
        warn(f"region {region.name}: carry compute in a multi-compute "
             "region; using gather fallback")
        return None
    multi_out = len(region.outputs) > 1
    if multi_out and carry is None and len(region.computes) > 1:
        warn(f"region {region.name}: {len(region.outputs)} output memories "
             "from a multi-compute region; tile emission needs a single "
             "compute (or a carry compute) — using gather fallback")
        return None
    if any(a is None for _c, _m, a in region.outputs):
        warn(f"region {region.name}: output access unknown")
        return None

    tile_fns = {}
    for c in region.computes:
        fn = _tile_fn_of(g, c)
        if fn is None and not (carry is not None and c == region.computes[0]):
            warn(f"region {region.name}: compute {c} has no per-tile body "
                 "(meta['tile_fn']); using gather fallback")
            return None
        if not region.bindings[c]:
            warn(f"region {region.name}: compute {c} has no operands")
            return None
        tile_fns[c] = fn

    def step_syms(c: str) -> Tuple[str, ...]:
        dom = g.nodes[c].domain
        return dom.symbols if dom is not None else ()

    outputs: List[Tuple[str, str, BlockedAccess]] = []
    for c, mem, acc in region.outputs:
        ba = blocked_access(acc, g.nodes[mem].shape, protect=step_syms(c))
        if ba is None:
            warn(f"region {region.name}: output access to {mem} is not "
                 "block-affine")
            return None
        outputs.append((c, mem, ba))
    out_block = outputs[0][2]

    blocks: Dict[Tuple[str, int], BlockedAccess] = {}
    extents: Dict[str, int] = dict(out_block.grid)
    extra_syms: List[str] = []
    for c in region.computes:
        for k, src in enumerate(region.bindings[c]):
            if src[0] != "mem":
                continue
            if src[2] is None:
                warn(f"region {region.name}: operand {src[1]} of {c} has "
                     "no access pattern")
                return None
            acc = blocked_access(src[2], g.nodes[src[1]].shape,
                                 protect=step_syms(c))
            if acc is None:
                warn(f"region {region.name}: operand {src[1]} of {c} is not "
                     "block-affine")
                return None
            for s, e in acc.grid:
                if extents.setdefault(s, e) != e:
                    warn(f"region {region.name}: grid extent mismatch on "
                         f"{s}: {extents[s]} vs {e}")
                    return None
                if s not in dict(out_block.grid) and s not in extra_syms:
                    extra_syms.append(s)
            blocks[(c, k)] = acc

    # canonical grid: output order first, extra symbols innermost
    grid = tuple(out_block.grid) + tuple((s, extents[s]) for s in extra_syms)
    reduce_syms = tuple(extra_syms)
    carry_syms: Tuple[str, ...] = ()
    outer_syms: Tuple[str, ...] = ()
    if multi_out and carry is None:
        # multi-output map (e.g. the SSD decode step's y + new state): every
        # output must be written exactly once per grid point, so reduction
        # symbols and grid mismatches between the outputs both disqualify
        # tile emission
        if extra_syms:
            warn(f"region {region.name}: multi-output region with reduction "
                 f"symbols {extra_syms}; using gather fallback")
            return None
        for _c, mem, ba in outputs[1:]:
            if tuple(ba.grid) != tuple(out_block.grid):
                warn(f"region {region.name}: output {mem} grid "
                     f"{ba.grid_symbols} differs from the region grid "
                     f"{out_block.grid_symbols}; using gather fallback")
                return None
    if carry is not None:
        # mixed carry+reduction first: naming the extra reduction symbols is
        # strictly more actionable than the generic innermost-axis message
        # (a serving-path regression to the gather tier must be diagnosable
        # from PipelineReport.warnings alone)
        mixed = [s for s in extra_syms if s != carry.axis]
        if mixed:
            warn(f"region {region.name}: mixed carry+reduction grid — "
                 f"carry axis {carry.axis!r} with extra reduction symbols "
                 f"{mixed}; using gather fallback")
            return None
        if not grid or grid[-1][0] != carry.axis:
            warn(f"region {region.name}: carry axis {carry.axis!r} is not "
                 "the innermost grid dimension; using gather fallback")
            return None
        carry_syms = (carry.axis,)
        reduce_syms = ()
        dom = g.nodes[region.computes[0]].domain
        outer_syms = tuple(s for s in dom.symbols if s != carry.axis)

    # full coverage is a *pre-temporal* property (the temporal rewrite
    # below moves extents between grid and block but never the product)
    covered = all(ba.covers(g.nodes[mem].shape) for _c, mem, ba in outputs)
    plan = RegionPlan(region=region, grid=grid, reduce_syms=reduce_syms,
                      blocks=blocks, outputs=outputs, tile_fns=tile_fns,
                      mode=region.mode, carry=carry, carry_syms=carry_syms,
                      outer_syms=outer_syms)
    _apply_temporal(g, plan, region.pump, warn)
    plan.pallas_ok = covered and _block_unit_ok(plan)
    return plan


def _append_pump(plan: RegionPlan, factor: int) -> None:
    """Insert the mode-R ``_pump`` grid axis.  For carry regions it goes
    *outside* the carry symbols (each sub-tile runs its own full sweep —
    interleaving sub-tiles inside a sweep would tear the carried state);
    otherwise innermost, walking the output sub-tiles per grid step."""
    if plan.carry_syms:
        idx0 = min(i for i, (s, _e) in enumerate(plan.grid)
                   if s in plan.carry_syms)
        plan.grid = plan.grid[:idx0] + ((PUMP_SYM, factor),) \
            + plan.grid[idx0:]
    else:
        plan.grid = tuple(plan.grid) + ((PUMP_SYM, factor),)


def _narrow_labelled(g: Graph, plan: RegionPlan, factor: int,
                     warn: Callable[[str], None]) -> bool:
    """Mode-R narrowing via the compute's declared axis correspondence
    (``meta['axes']``): narrow every block dimension labelled with the
    compute's ``narrow`` axis — output(s), operands and carry state alike.
    Exact by construction: a dimension is narrowed because the compute says
    it corresponds, not because its size or grid symbol happens to match.
    """
    comp = plan.out_compute
    axes = g.nodes[comp].meta.get("axes")
    name = axes.get("narrow") if axes else None
    if not name:
        return False
    out_maps, in_maps = axes.get("outs", ()), axes.get("ins", ())
    carry_maps = axes.get("carry", ())

    def dim_of(mapping) -> Optional[int]:
        hits = [d for d, nm in mapping.items() if nm == name]
        return hits[0] if hits else None

    d0 = dim_of(out_maps[0]) if out_maps else None
    if d0 is None or plan.outputs[0][2].block[d0] % factor:
        warn(f"region {plan.region.name}: mode-R axis {name!r} not "
             f"divisible by pump factor {factor}; temporal axis dropped")
        return True     # handled (by dropping), do not fall back
    new_outs = []
    for oi, (c, mem, ba) in enumerate(plan.outputs):
        d = dim_of(out_maps[oi]) if oi < len(out_maps) else None
        new_outs.append((c, mem, narrow_block(ba, d, factor)
                         if d is not None else ba))
    plan.outputs = new_outs
    narrowed = {}
    for (c, k), acc in plan.blocks.items():
        d = dim_of(in_maps[k]) if c == comp and k < len(in_maps) else None
        narrowed[(c, k)] = narrow_block(acc, d, factor) \
            if d is not None else acc
    plan.blocks = narrowed
    for si, mapping in enumerate(carry_maps):
        d = dim_of(mapping)
        if d is not None:
            plan.carry_narrow[si] = (d, factor)
    _append_pump(plan, factor)
    plan.pump = factor
    return True


def _apply_temporal(g: Graph, plan: RegionPlan, factor: int,
                    warn: Callable[[str], None]) -> None:
    """Realize pump factor M as the innermost ``_pump`` grid axis."""
    if factor <= 1:
        return
    if plan.mode == "T":
        if not plan.grid:
            warn(f"region {plan.region.name}: no grid dimension to pump")
            return
        sym, ext = plan.grid[-1]
        if ext % factor:
            warn(f"region {plan.region.name}: innermost grid extent {ext} "
                 f"({sym}) not divisible by pump factor {factor}; temporal "
                 "axis dropped")
            return
        try:
            plan.blocks = {k: split_temporal(a, sym, factor)
                           for k, a in plan.blocks.items()}
            plan.outputs = [(c, mem, split_temporal(ba, sym, factor))
                            for c, mem, ba in plan.outputs]
        except ValueError as err:    # e.g. a group-indexed (table) symbol
            warn(f"region {plan.region.name}: cannot split {sym}: {err}; "
                 "temporal axis dropped")
            return
        grid = [(s, e // factor if s == sym else e) for s, e in plan.grid]
        plan.grid = tuple(grid) + ((PUMP_SYM, factor),)
        if sym in plan.reduce_syms:
            plan.reduce_syms = plan.reduce_syms + (PUMP_SYM,)
        if sym in plan.carry_syms:
            # the M beats of one wide transaction continue the sweep
            plan.carry_syms = plan.carry_syms + (PUMP_SYM,)
        plan.pump = factor
        return
    # ---- mode R: narrow the output-carrying block dimension(s) -------------
    if _narrow_labelled(g, plan, factor, warn):
        return
    if plan.carry is not None:
        warn(f"region {plan.region.name}: carry region without a mode-R "
             "axis correspondence (meta['axes']); temporal axis dropped")
        return
    out = plan.out_block
    d_out = max((d for d, b in enumerate(out.block) if b > 1),
                default=None)
    if d_out is None or out.block[d_out] % factor:
        warn(f"region {plan.region.name}: mode-R output block not "
             f"divisible by pump factor {factor}; temporal axis dropped")
        return
    b_wide = out.block[d_out]
    dep = out.offsets[d_out]
    c0, mem0, _ = plan.outputs[0]
    plan.outputs = [(c0, mem0, narrow_block(out, d_out, factor))]
    narrowed = {}
    for key, acc in plan.blocks.items():
        new = acc
        for d in reversed(range(len(acc.block))):
            # dataflow correspondence: the operand dimension walks the
            # same offset expression as the output dimension being
            # narrowed (symbol-set matching is not enough — see the
            # mode-R regression tests)
            if acc.block[d] == b_wide and acc.offsets[d] == dep:
                new = narrow_block(acc, d, factor)
                break
        narrowed[key] = new
    plan.blocks = narrowed
    _append_pump(plan, factor)
    plan.pump = factor


def _block_unit_ok(plan: RegionPlan) -> bool:
    """True when every access (operands and outputs) has a block-unit index
    map — the post-temporal half of pallas expressibility."""
    return all(ba.block_unit_offsets() is not None
               for _c, _m, ba in plan.outputs) \
        and all(a.block_unit_offsets() is not None
                for a in plan.blocks.values())


# ----------------------------------------------------------- TPU tiling --
@dataclasses.dataclass(frozen=True)
class TileView:
    """A free (row-major) reshape of one memory under which its block obeys
    the TPU tiling rule: the last block dim a multiple of 128 or the whole
    dim, the second-to-last a multiple of 8 or the whole dim (rank-1 blocks:
    a multiple of XLA's 1-D tile or the whole array).

    ``kind`` says how block-unit offsets map into the view:
    ``same`` (no reshape), ``unit`` (a unit axis inserted before the last
    dim, so a ``(…, 1, X)`` block becomes ``(…, 1, 1, X)`` — the kernel's
    reshape back only touches leading dims) and ``flat`` (a rank-1 memory
    of ``n`` elements in blocks of ``b`` viewed as ``(n // b, 1, b)``)."""

    shape: Tuple[int, ...]
    block: Tuple[int, ...]
    kind: str = "same"

    def index(self, offs: Tuple) -> Tuple:
        if self.kind == "unit":
            return tuple(offs[:-1]) + (0,) + tuple(offs[-1:])
        if self.kind == "flat":
            return (offs[0], 0, 0)
        return tuple(offs)


def _tiling_ok(shape: Tuple[int, ...], block: Tuple[int, ...],
               itemsize: int) -> bool:
    if len(block) == 1:
        # XLA lays a rank-1 array out in tiles of 1024 32-bit words, and the
        # kernel's operand layout must match it
        return block[0] == shape[0] or block[0] % (1024 * (4 // itemsize
                                                           or 1)) == 0
    return ((block[-1] == shape[-1] or block[-1] % 128 == 0)
            and (block[-2] == shape[-2] or block[-2] % 8 == 0))


def tile_view(shape: Tuple[int, ...], block: Tuple[int, ...],
              itemsize: int) -> Optional[TileView]:
    """The :class:`TileView` that makes ``block`` legal on TPU, or None when
    no unit-axis reshape can (e.g. a last block dim that is a sub-lane
    slice of a longer dim)."""
    shape, block = tuple(shape), tuple(block)
    if _tiling_ok(shape, block, itemsize):
        return TileView(shape, block)
    if len(block) == 1:
        n, b = shape[0], block[0]
        return TileView((n // b, 1, b), (1, 1, b), "flat") \
            if n % b == 0 else None
    if block[-2] == 1 and (block[-1] == shape[-1] or block[-1] % 128 == 0):
        return TileView(shape[:-1] + (1, shape[-1]),
                        block[:-1] + (1, block[-1]), "unit")
    return None


def _views(g: Graph, plan: RegionPlan):
    """(operand views keyed like ``plan.blocks``, output views) — None for
    any access no :class:`TileView` legalizes."""
    def view(mem: str, ba: BlockedAccess) -> Optional[TileView]:
        node = g.nodes[mem]
        return tile_view(node.shape, ba.block, np.dtype(node.dtype).itemsize)

    ins = {key: view(plan.region.bindings[key[0]][key[1]][1], acc)
           for key, acc in plan.blocks.items()}
    outs = [view(mem, ba) for _c, mem, ba in plan.outputs]
    return ins, outs


def tpu_tiling_ok(g: Graph, plan: RegionPlan) -> bool:
    """True when every operand and output block of ``plan`` can be laid out
    legally for the TPU compiler (the real-chip half of pallas
    expressibility; interpret mode has no tiling rule)."""
    ins, outs = _views(g, plan)
    return all(v is not None for v in ins.values()) \
        and all(v is not None for v in outs)


# ---------------------------------------------------------------- emission --
def _affine_eval(a: Affine, env: Mapping[str, Any]):
    out = a.const
    for s, c in a.terms:
        out = out + c * env[s]
    for s, t in a.tables:
        # group-indexed lookup: static table, traced (grid) index
        out = out + jnp.asarray(np.asarray(t, dtype=np.int32))[env[s]]
    return out


def _carry_predicates(plan: RegionPlan, env: Mapping[str, Any]):
    """(first, last, step, idx-kwargs) for one grid point of a carry plan."""
    exts = dict(plan.grid)
    first = functools.reduce(
        jnp.logical_and, [env[s] == 0 for s in plan.carry_syms])
    last = functools.reduce(
        jnp.logical_and,
        [env[s] == exts[s] - 1 for s in plan.carry_syms])
    step = 0
    for s in plan.carry_syms:
        step = step * exts[s] + env[s]
    kwargs = {}
    if plan.carry.pass_idx:
        kwargs["idx"] = dict(
            step=step,
            outer=tuple(env[s] for s in plan.outer_syms),
            pump=env.get(PUMP_SYM, 0) if PUMP_SYM not in plan.carry_syms
            else 0)
    return first, last, kwargs


def _run_tiles(plan: RegionPlan, get_block: Callable[[str, int], Any]) -> Any:
    """Evaluate the region's compute chain for one grid point;
    ``get_block(compute, operand_idx)`` supplies memory operand blocks."""
    tiles: Dict[str, Any] = {}
    for c in plan.region.computes:
        bound = {}
        for k, src in enumerate(plan.region.bindings[c]):
            if src[0] == "mem":
                bound[f"in{k}"] = get_block(c, k)
            else:
                bound[f"in{k}"] = tiles[src[1]]
        r = plan.tile_fns[c](**bound)
        tiles[c] = r["out0"] if isinstance(r, dict) else r
    return tiles[plan.out_compute]


def emit_blockloop(g: Graph, plan: RegionPlan) -> Callable:
    """Tier ``blockloop``: the pallas schedule as a fused ``fori_loop`` with
    element-unit ``dynamic_slice`` blocks — the jit fallback on CPU.  Carry
    plans thread the loop-carried state through the ``fori_loop`` carry and
    may write several output memories; region functions return
    ``{memory name: array}``."""
    grid = plan.grid
    sizes = [e for _, e in grid]
    total = int(np.prod(sizes)) if sizes else 1

    def unflatten(step) -> Dict[str, Any]:
        env: Dict[str, Any] = {}
        rem = step
        for (sym, ext) in reversed(grid):
            env[sym] = rem % ext
            rem = rem // ext
        return env

    def make_get_block(mems, env):
        def get_block(c, k):
            acc = plan.blocks[(c, k)]
            mem = mems[plan.region.bindings[c][k][1]]
            starts = tuple(_affine_eval(a, env) for a in acc.offsets)
            return jax.lax.dynamic_slice(mem, starts, acc.block)
        return get_block

    def write_block(buf, ba: BlockedAccess, env, tile):
        tile = jnp.reshape(tile, ba.block).astype(buf.dtype)
        starts = tuple(_affine_eval(a, env) for a in ba.offsets)
        return jax.lax.dynamic_update_slice(buf, tile, starts)

    if plan.carry is not None:
        spec = plan.carry
        mems_order = [mem for _c, mem, _ba in plan.outputs]
        n_step_out = spec.n_step_outs(len(plan.outputs))

        def region_fn(mems: Dict[str, Any]) -> Dict[str, Any]:
            init_state = tuple(
                jnp.asarray(a)
                for a in spec.init_arrays(jnp, narrow=plan.carry_narrow))
            bufs0 = tuple(mems[m] for m in mems_order)

            def body(step, st):
                carry, bufs = st
                env = unflatten(step)
                first, last, kwargs = _carry_predicates(plan, env)
                carry = tuple(jnp.where(first, ini, cur)
                              for ini, cur in zip(init_state, carry))
                get_block = make_get_block(mems, env)
                blocks = [get_block(plan.out_compute, k)
                          for k in range(
                              len(plan.region.bindings[plan.out_compute]))]
                carry2, souts = spec.step_fn(carry, *blocks, **kwargs)
                new_bufs = list(bufs)
                for k in range(n_step_out):
                    _c, _m, ba = plan.outputs[k]
                    new_bufs[k] = write_block(bufs[k], ba, env,
                                              souts[f"out{k}"])
                if spec.final_fn is not None:
                    fouts = spec.final_fn(carry2)
                    for k in range(n_step_out, len(plan.outputs)):
                        _c, _m, ba = plan.outputs[k]
                        new_bufs[k] = jnp.where(
                            last,
                            write_block(bufs[k], ba, env, fouts[f"out{k}"]),
                            bufs[k])
                return carry2, tuple(new_bufs)

            _carry, bufs = jax.lax.fori_loop(0, total, body,
                                             (init_state, bufs0))
            return dict(zip(mems_order, bufs))

        return region_fn

    if len(plan.outputs) > 1:
        # multi-output map: one tile_fn call per grid point writes every
        # output block (no reduction symbols by plan construction)
        mems_order = [mem for _c, mem, _ba in plan.outputs]
        comp = plan.out_compute
        n_ops = len(plan.region.bindings[comp])

        def region_fn(mems: Dict[str, Any]) -> Dict[str, Any]:
            def body(step, bufs):
                env = unflatten(step)
                get_block = make_get_block(mems, env)
                r = plan.tile_fns[comp](
                    **{f"in{k}": get_block(comp, k) for k in range(n_ops)})
                return tuple(
                    write_block(buf, ba, env, r[f"out{k}"])
                    for k, (buf, (_c, _m, ba))
                    in enumerate(zip(bufs, plan.outputs)))

            bufs = jax.lax.fori_loop(0, total, body,
                                     tuple(mems[m] for m in mems_order))
            return dict(zip(mems_order, bufs))

        return region_fn

    out_mem, out_block = plan.out_mem, plan.out_block

    def region_fn(mems: Dict[str, Any]) -> Dict[str, Any]:
        def body(step, buf):
            env = unflatten(step)
            tile = _run_tiles(plan, make_get_block(mems, env))
            tile = jnp.reshape(tile, out_block.block).astype(buf.dtype)
            starts = tuple(_affine_eval(a, env) for a in out_block.offsets)
            if plan.reduce_syms:
                first = functools.reduce(
                    jnp.logical_and,
                    [env[s] == 0 for s in plan.reduce_syms])
                prev = jax.lax.dynamic_slice(buf, starts, out_block.block)
                tile = jnp.where(first, tile, prev + tile)
            return jax.lax.dynamic_update_slice(buf, tile, starts)

        init = mems[out_mem]
        return {out_mem: jax.lax.fori_loop(0, total, body, init)}

    return region_fn


def emit_pallas(g: Graph, plan: RegionPlan, interpret: bool) -> Callable:
    """Tier ``pallas``: one ``pl.pallas_call`` for the whole region, block
    specs and index maps derived from the symbolic access patterns.  Carry
    plans keep their state in VMEM scratch with ``pl.when``-gated sweep
    init/finalize — the hand-written flash-attention schedule, derived.

    Every memory is passed through its :class:`TileView`, so the block specs
    obey the TPU tiling rule; the kernel body reshapes each view block back
    to the access's own block shape, so tile bodies never see the view.
    Accesses no view legalizes keep their raw block (interpret mode only —
    :func:`lower_pallas` routes such plans off this tier on a chip).  The
    scoped VMEM limit is the budget the pump planner plans against."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    grid_sizes = tuple(e for _, e in plan.grid)
    syms = [s for s, _ in plan.grid]
    red_axes = [i for i, (s, _) in enumerate(plan.grid)
                if s in plan.reduce_syms]
    params = pltpu.CompilerParams(vmem_limit_bytes=VMEM_BYTES)

    mem_order: List[Tuple[str, int]] = []    # (compute, operand idx), flat
    for c in plan.region.computes:
        for k, src in enumerate(plan.region.bindings[c]):
            if src[0] == "mem":
                mem_order.append((c, k))

    in_views, out_views = _views(g, plan)
    for key in mem_order:
        if in_views[key] is None:
            mem = g.nodes[plan.region.bindings[key[0]][key[1]][1]]
            in_views[key] = TileView(mem.shape, plan.blocks[key].block)
    out_views = [v if v is not None
                 else TileView(g.nodes[mem].shape, ba.block)
                 for v, (_c, mem, ba) in zip(out_views, plan.outputs)]

    def load(ref, acc: BlockedAccess):
        """One operand block, in the access's own block shape."""
        return jnp.reshape(ref[...], acc.block)

    def store(ref, val) -> None:
        ref[...] = jnp.reshape(val, ref.shape).astype(ref.dtype)

    def operands(mems):
        return [jnp.reshape(mems[plan.region.bindings[c][k][1]],
                            in_views[(c, k)].shape) for c, k in mem_order]

    def results(outs) -> Dict[str, Any]:
        return {mem: jnp.reshape(o, g.nodes[mem].shape)
                for (_c, mem, _ba), o in zip(plan.outputs, outs)}

    def index_map_for(acc: BlockedAccess, view: TileView):
        offs = acc.block_unit_offsets()

        def eval_scalar(a: Affine, env):
            # pallas index maps must not capture constant arrays, so
            # group-indexed tables unroll to a select-sum over scalar
            # comparisons instead of a gather (tables are small: per-head
            # or per-tile ids)
            out = a.const
            for s, c in a.terms:
                out = out + c * env[s]
            for s, t in a.tables:
                for j, v in enumerate(t):
                    if v:
                        out = out + v * (env[s] == j)
            return out

        def index_map(*gids):
            env = dict(zip(syms, gids))
            return view.index(tuple(eval_scalar(a, env) for a in offs))

        return index_map

    in_specs = [pl.BlockSpec(in_views[key].block,
                             index_map_for(plan.blocks[key], in_views[key]))
                for key in mem_order]
    out_specs = [pl.BlockSpec(v.block, index_map_for(ba, v))
                 for v, (_c, _m, ba) in zip(out_views, plan.outputs)]
    out_shapes = [jax.ShapeDtypeStruct(v.shape, g.nodes[mem].dtype)
                  for v, (_c, mem, _ba) in zip(out_views, plan.outputs)]
    n_out = len(plan.outputs)
    in_accs = [plan.blocks[key] for key in mem_order]

    def call(kernel, mems, **kw):
        outs = pl.pallas_call(
            kernel, grid=grid_sizes, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shapes, compiler_params=params,
            interpret=interpret, **kw)(*operands(mems))
        return results(outs)

    if plan.carry is not None:
        spec = plan.carry
        n_step_out = spec.n_step_outs(n_out)
        state_shapes = []
        for i, entry in enumerate(spec.state):
            shape = entry[0]
            if i in plan.carry_narrow:
                d, factor = plan.carry_narrow[i]
                shape = tuple(s // factor if j == d else s
                              for j, s in enumerate(shape))
            state_shapes.append((shape, entry[1]))
        scratch_shapes = [pltpu.VMEM(shape, jnp.dtype(dt))
                          for shape, dt in state_shapes]
        # scalar fills, not captured init arrays: a pallas kernel body must
        # not close over constant arrays
        fills = [float(entry[2]) if len(entry) > 2 else 0.0
                 for entry in spec.state]

        def kernel(*refs):
            in_refs = refs[:len(mem_order)]
            out_refs = refs[len(mem_order):len(mem_order) + n_out]
            st_refs = refs[len(mem_order) + n_out:]
            env = {s: pl.program_id(i) for i, s in enumerate(syms)}
            first, last, kwargs = _carry_predicates(plan, env)

            @pl.when(first)
            def _init():
                for ref, fill in zip(st_refs, fills):
                    ref[...] = jnp.full(ref.shape, fill, ref.dtype)

            blocks = [load(r, a) for r, a in zip(in_refs, in_accs)]
            carry = tuple(r[...] for r in st_refs)
            carry2, souts = spec.step_fn(carry, *blocks, **kwargs)
            for ref, val in zip(st_refs, carry2):
                ref[...] = val
            for k in range(n_step_out):
                store(out_refs[k], souts[f"out{k}"])
            if spec.final_fn is not None:
                fouts = spec.final_fn(carry2)

                @pl.when(last)
                def _finish():
                    for k in range(n_step_out, n_out):
                        store(out_refs[k], fouts[f"out{k}"])

        return lambda mems: call(kernel, mems, scratch_shapes=scratch_shapes)

    if n_out > 1:
        # multi-output map: no reduction symbols (plan construction), every
        # out_ref written per grid point
        comp = plan.out_compute
        n_ops = len(plan.region.bindings[comp])

        def kernel(*refs):
            in_refs, out_refs = refs[:len(mem_order)], refs[len(mem_order):]
            blocks = {key: load(r, a)
                      for key, r, a in zip(mem_order, in_refs, in_accs)}
            r = plan.tile_fns[comp](
                **{f"in{k}": blocks[(comp, k)] for k in range(n_ops)})
            for k, ref in enumerate(out_refs):
                store(ref, r[f"out{k}"])

        return lambda mems: call(kernel, mems)

    def kernel(*refs):
        in_refs, (o_ref,) = refs[:-1], refs[-1:]
        blocks = {key: load(r, a)
                  for key, r, a in zip(mem_order, in_refs, in_accs)}
        tile = _run_tiles(plan, lambda c, k: blocks[(c, k)])
        tile = jnp.reshape(tile, o_ref.shape).astype(o_ref.dtype)
        if red_axes:
            first = functools.reduce(
                jnp.logical_and, [pl.program_id(a) == 0 for a in red_axes])

            @pl.when(first)
            def _init():
                o_ref[...] = tile

            @pl.when(jnp.logical_not(first))
            def _acc():
                o_ref[...] += tile
        else:
            o_ref[...] = tile

    return lambda mems: call(kernel, mems)


def emit_gather(g: Graph, region: Region) -> Callable:
    """Tier ``gather``: region-level fallback — one fused gather →
    compute-chain → scatter, addresses frozen from the access patterns.
    Multi-output computes scatter each named output; carry computes run
    the ``fori_loop`` sequence form shared with the per-node lowering."""
    carry_fns: Dict[str, Callable] = {}
    idx_in: Dict[Tuple[str, int], np.ndarray] = {}
    for c in region.computes:
        if g.nodes[c].meta.get("carry") is not None:
            carry_fns[c] = carry_sequence_apply(g, g.nodes[c])
        elif g.nodes[c].fn is None:
            raise LoweringError(
                f"compute module {c!r} has no fn body to lower")
        for k, src in enumerate(region.bindings[c]):
            if src[0] == "mem":
                if src[2] is None:
                    raise LoweringError(
                        f"operand {k} of {c} has no access pattern")
                idx_in[(c, k)] = _indices(src[2], g.nodes[src[1]].shape)
    # per compute: (out-edge position, sink memory, scatter indices) —
    # keyed by edge position so output name binding (out0, out1, ...)
    # matches the executor's edge-order convention
    idx_out: Dict[str, List[Tuple[int, str, np.ndarray]]] = {}
    for c in region.computes:
        for kpos, e in enumerate(g.out_edges(c)):
            sunk = _trace_to_sink(g, e)
            if sunk is not None:
                mem, access = sunk
                idx_out.setdefault(c, []).append(
                    (kpos, mem,
                     scatter_indices(access, g.nodes[mem].shape,
                                     where=f"{c}->{mem}")))

    def region_fn(mems: Dict[str, Any]) -> Dict[str, Any]:
        tiles: Dict[str, Any] = {}
        results: Dict[str, Dict[str, Any]] = {}
        for c in region.computes:
            bound = {}
            for k, src in enumerate(region.bindings[c]):
                if src[0] == "mem":
                    flat = jnp.reshape(mems[src[1]], (-1,))
                    bound[f"in{k}"] = jnp.take(flat, idx_in[(c, k)])
                else:
                    bound[f"in{k}"] = tiles[src[1]]
            if c in carry_fns:
                r = carry_fns[c](bound)
            else:
                r = g.nodes[c].fn(**bound)
            if not isinstance(r, dict):
                r = {"out0": r}
            results[c] = r
            tiles[c] = r["out0"]
        outs = {}
        for c, sinks in idx_out.items():
            for kpos, mem, idx in sinks:
                target = outs.get(mem, mems[mem])
                vals = jnp.reshape(jnp.asarray(results[c][f"out{kpos}"]),
                                   (-1,)).astype(target.dtype)
                flat = jnp.reshape(target, (-1,))
                outs[mem] = jnp.reshape(flat.at[idx].set(vals), target.shape)
        return outs

    return region_fn


# ------------------------------------------------------------------ driver --
def lower_pallas(g: Graph, jit: bool = True, pallas_mode: str = "auto",
                 warn: Optional[Callable[[str], None]] = None,
                 emission: Optional[dict] = None
                 ) -> Callable[[Mapping[str, Any]], Dict[str, jax.Array]]:
    """Lower ``g`` through the fused-region pallas backend.

    ``pallas_mode``: ``'auto'`` emits real ``pl.pallas_call`` kernels only
    when a TPU is attached (CPU gets the ``blockloop`` jit fallback),
    ``'interpret'`` forces ``pl.pallas_call(interpret=True)`` for pallas-
    expressible regions (validation path), ``'fallback'`` never emits
    pallas calls.  ``emission`` (a dict) receives per-region provenance.
    """
    if pallas_mode not in ("auto", "interpret", "fallback"):
        raise ValueError(f"unknown pallas_mode {pallas_mode!r}")
    faults.check("emission.lower", graph=g.name)
    g.validate()
    warn = warn or (lambda msg: None)
    on_tpu = any(d.platform == "tpu" for d in jax.devices())
    use_pallas = pallas_mode == "interpret" or \
        (pallas_mode == "auto" and on_tpu)
    # 'interpret' is a validation contract: force the interpreter even on
    # TPU; 'auto' interprets only when no TPU can compile the kernel
    interpret = pallas_mode == "interpret" or not on_tpu

    regions = partition_regions(g)
    emitted: List[Tuple[Region, str, Callable]] = []
    tag = ""            # _m<pump><mode> of the first region, for the name
    for region in regions:
        notes: List[str] = []
        plan = plan_region(g, region, notes.append)
        if not emitted:
            tag = f"_m{plan.pump if plan else region.pump}{region.mode}"
        if plan is not None and use_pallas and plan.pallas_ok \
                and not interpret and not tpu_tiling_ok(g, plan):
            notes.append(f"region {region.name}: a block breaks the TPU "
                         "tiling rule under every unit-axis view; not "
                         "emitted as a pallas kernel")
            plan.pallas_ok = False
        for n in notes:
            warn(n)
        if plan is not None and use_pallas and plan.pallas_ok:
            tier = "pallas"
            fn = emit_pallas(g, plan, interpret=interpret)
        elif plan is not None:
            tier = "carryloop" if plan.carry is not None else "blockloop"
            fn = emit_blockloop(g, plan)
        else:
            tier = "gather"
            fn = emit_gather(g, region)
        # per-region tier decision is a first-class observable: the tier mix
        # (how much of a model emits at which tier) lands in the metrics
        # snapshot, and a downgrade carries its reason — a serving-path
        # regression to the slow tier must be attributable from telemetry
        # alone, not only from a PipelineReport someone kept around
        obs.count(f"emission.tier.{tier}", graph=g.name, region=region.name)
        if plan is not None:
            # a kernel's cost follows its grid steps as much as its bytes:
            # device time / (calls × grid_points) is the time per step
            obs.count("emission.grid_points",
                      int(np.prod([e for _s, e in plan.grid])),
                      graph=g.name, region=region.name)
        if notes:
            obs.count("emission.degraded", graph=g.name,
                      region=region.name, tier=tier, why="; ".join(notes))
        if emission is not None:
            emission[region.name] = {
                "tier": tier,
                "pump": plan.pump if plan is not None else 1,
                "mode": region.mode,
                "grid": [list(d) for d in plan.grid] if plan else None,
                "reduce": list(plan.reduce_syms) if plan else None,
                "carry": list(plan.carry_syms) if plan else None,
                "outputs": [mem for _c, mem, _a in region.outputs],
                # degradation provenance: why this region did not emit at a
                # higher tier (mirrors the PipelineReport warning strings)
                "why": list(notes),
            }
        emitted.append((region, tier, fn))

    def run_fn(inputs: Mapping[str, Any]) -> Dict[str, jax.Array]:
        mems: Dict[str, jax.Array] = {}
        for n in g.nodes.values():
            if n.kind != NodeKind.MEMORY:
                continue
            if n.name in inputs:
                mems[n.name] = jnp.asarray(inputs[n.name], dtype=n.dtype)
            else:
                mems[n.name] = jnp.zeros(n.shape, dtype=n.dtype)
        for _region, _tier, fn in emitted:
            mems.update(fn(mems))
        return mems

    # chaos seam: lets tests simulate a compiled kernel that runs but
    # produces garbage (NaNs) or dies at execution time — a no-op (the
    # original run_fn) unless fault rules are installed at lowering time
    run_fn = faults.wrap("emission.exec", run_fn, graph=g.name)
    # the jitted wrapper's name becomes the HLO name of the kernel's custom
    # call (``pallas_call(name=...)`` only sets an attribute), so a profiler
    # trace tells the kernels apart: ``decode_attention_m1T.5``
    run_fn.__name__ = run_fn.__qualname__ = re.sub(r"\W", "_", g.name + tag)
    return jax.jit(run_fn) if jit else run_fn
