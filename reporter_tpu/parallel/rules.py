"""The (kind, kernel) program family's partition-rule table.

One declarative ``(regex, PartitionSpec)`` table names how every argument
and result of every packed matcher program shards over the replica's
device mesh (docs/performance.md "One logical matcher per pod").  Before
this table the mesh was a *sibling code path*: bespoke ``_make_gp_*``
shard_map twins hand-listed in_specs per program and stepped aside for
tiering, sparse dispatch, and the session arena.  Now partitioning is an
axis of the program family — the SAME rules drive

  - the GSPMD device_put shardings of the plain-jit path (a dp-only mesh:
    committed inputs make the unmodified jits run SPMD), and
  - the in/out specs of the generic shard_map builder
    (``SegmentMatcher._build_program``) that the gp-sharded probe needs
    (``axis_index``/``pmin`` are not expressible in plain GSPMD).

Rules are matched over the program pytree by NAME, in the style of the
classic ``match_partition_rules`` used by large pjit codebases
(SNIPPETS.md): leaf paths join with "/", the first matching rule wins,
scalar and size-1 leaves replicate regardless, and an unmatched leaf is
an error — a new program argument must be placed deliberately, never
sharded by accident.

What the table says (and why):

  dg / p / sp    replicated — read-only graph arrays and traced scalar
                 parameter bundles every shard needs.
  du             bucket-range over "gp": the UBODT's array leaves (the
                 packed table, or a tiered table's hot arena + slot map +
                 cold pages) split by the SAME contiguous-bucket
                 partition the fleet sharding uses
                 (tiles/tiering.shard_bucket_range).  On a mesh without a
                 gp axis this resolves to replicated.
  xin / packed   [·, B, T] packed transport: batch axis (axis 1) over
                 "dp" — the candidate quadrant sweep, emissions, and the
                 K-state recursion all ride the batch axis; the [K]
                 recursion itself stays local per docs/pallas-decision.md.
  pre / carry /  leading-[B] pytrees, the [B, 4] confidence block and
  aux / probe    the [B, 3] probe-path step counts: row-sharded over "dp"
                 alongside the batch.
  slab           the session arena's [S]-slot beam slab: slot-sharded
                 over "dp" — a replica's carried beams live in POD-level
                 HBM, not one chip's.
  slots / use    replicated [B] slot indices / carry masks: every dp
                 shard needs the full map to resolve which slab rows it
                 owns (ops/viterbi mesh arena step).
"""

from __future__ import annotations

import re
from typing import Sequence, Tuple

from jax.sharding import PartitionSpec as P

BATCH_AXIS = "dp"
GRAPH_AXIS = "gp"


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with this codebase's default of no
    varying-manual-axes check (every shard_map goes through here)."""
    import jax

    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)

# the one table.  Order matters: first match wins.
PROGRAM_RULES: Tuple[Tuple[str, P], ...] = (
    (r"^(dg|p|sp)(/|$)", P()),
    (r"^du(/|$)", P(GRAPH_AXIS)),
    (r"^(xin|packed)(/|$)", P(None, BATCH_AXIS)),
    (r"^(pre|carry|aux|probe)(/|$)", P(BATCH_AXIS)),
    (r"^slab(/|$)", P(BATCH_AXIS)),
    (r"^(slots|use)(/|$)", P()),
)


def resolve_spec(spec: P, axis_names: Sequence[str]) -> P:
    """A rule's PartitionSpec against a concrete mesh: axes the mesh does
    not carry resolve to None (replicated on that dimension).  This is
    what makes ONE table serve every topology — ``du -> P("gp")`` shards
    the table by bucket range on a dp×gp mesh and replicates it on a
    dp-only mesh, with no second rule set."""
    names = set(axis_names)
    return P(*(a if a in names else None for a in spec))


def match_partition_rules(rules, tree, axis_names: Sequence[str] = (
        BATCH_AXIS, GRAPH_AXIS)):
    """PartitionSpec pytree for ``tree``, by named leaf path.

    The SNIPPETS.md idiom: flatten with paths, join key names with "/",
    scalar/size-1 leaves get P() (nothing to shard), otherwise the first
    rule whose regex ``re.search``-matches the name wins.  No match is a
    ValueError — every program leaf must be placed by the table."""
    import jax

    def _name(path) -> str:
        parts = []
        for k in path:
            if hasattr(k, "name"):
                parts.append(str(k.name))
            elif hasattr(k, "key"):
                parts.append(str(k.key))
            elif hasattr(k, "idx"):
                parts.append(str(k.idx))
            else:
                parts.append(str(k))
        return "/".join(parts)

    def _one(path, leaf):
        if getattr(leaf, "ndim", 0) == 0 or getattr(leaf, "size", 2) <= 1:
            return P()
        name = _name(path)
        for rule, spec in rules:
            if re.search(rule, name):
                return resolve_spec(spec, axis_names)
        raise ValueError(
            "no partition rule matches program leaf %r "
            "(parallel/rules.PROGRAM_RULES)" % (name,))

    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return jax.tree_util.tree_unflatten(
        treedef, [_one(path, leaf) for path, leaf in flat])


def spec_for(name: str, mesh=None) -> P:
    """The rule table's PartitionSpec for one named program argument —
    the pytree-PREFIX form both consumers use (one spec covers every leaf
    of that argument's subtree; jax broadcasts prefixes).  ``mesh``
    resolves axes the mesh lacks to replicated; None keeps the abstract
    spec."""
    for rule, spec in PROGRAM_RULES:
        if re.search(rule, name):
            if mesh is None:
                return spec
            return resolve_spec(spec, mesh.axis_names)
    raise ValueError(
        "no partition rule matches program argument %r "
        "(parallel/rules.PROGRAM_RULES)" % (name,))


def sharding_for(name: str, mesh):
    """NamedSharding for one named program argument on ``mesh`` — the
    device_put face of the table (the GSPMD plain-jit path: computation
    follows committed data)."""
    from jax.sharding import NamedSharding

    return NamedSharding(mesh, spec_for(name, mesh))
