"""StateArena: one spec layer under every serving engine.

Five serving subsystems (the engine, the speculative engine, block
migration, prefix spill/restore, fleet replicas) each hand-manage
donated device state.  The arena centralises the three things they all
re-prove independently:

* **placement** — every declared leaf (weight pytree, KV block pools,
  per-token scale pools) gets a resolved :class:`NamedSharding` spec via
  ``distributed/sharding_utils.infer_partition_specs`` /
  ``validate_spec``.  With no mesh the arena is a pass-through: values
  are committed with ``jnp.asarray`` and behaviour is bit-identical to
  the pre-arena engines.
* **donation** — pools are rebound through :meth:`bind` after each
  donated dispatch; the donated output of a sharded program carries the
  input sharding, so no re-placement (and no host transfer) happens on
  the steady-state path.
* **compilation** — :meth:`program` fronts the per-model shared program
  store with an LRU'd compile cache (``serving.arena.program_*``
  counters) so retrace accounting has one owner.

Sharding contract (the PagedAttention trick): block tables and sampling
parameters stay replicated int32 *operands* — only the KV pools
``[L, n_blocks, bs, nh/mp, hd]`` and the weight matrices shard, over the
``mp`` mesh axis.  Cross-chip reduction is an in-graph collective
inserted by GSPMD at the proj/fc2 contractions; the host never launches
a collective (``dist.collective_launches`` stays 0).

``nh`` not divisible by ``mp`` soft-degrades the head axis to replicated
(counter ``serving.mesh.spec_degraded``) instead of failing at compile
time, so one rule set serves several mesh shapes.
"""

from __future__ import annotations

from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..distributed.sharding_utils import (infer_partition_specs,
                                          validate_spec)
from ..profiler import counters

# Megatron-style tensor-parallel rules for the GPT decode_state tree,
# matched against '/'-joined leaf paths.  Column-parallel qkv/fc1 (shard
# the output features), row-parallel proj/fc2 (shard the input features;
# GSPMD inserts the all-reduce at the contraction).  Embeddings and the
# LM head shard their feature/vocab axis.  First match wins; unmatched
# leaves replicate.
DEFAULT_SHARD_RULES = (
    (r"qkv_w$", P(None, None, "mp")),
    (r"qkv_b$", P(None, "mp")),
    (r"proj_w$", P(None, "mp", None)),
    (r"fc1_w$", P(None, None, "mp")),
    (r"fc1_b$", P(None, "mp")),
    (r"fc2_w$", P(None, "mp", None)),
    (r"wte$", P(None, "mp")),
    (r"wpe$", P(None, "mp")),
    (r"head$", P("mp", None)),
)

# KV block pools [L, n_blocks, bs, nh, hd] shard the head axis.
KV_POOL_SPEC = P(None, None, None, "mp", None)

# every collective kind GSPMD may insert for the TP contraction pattern;
# programs audited with this allowlist may contain them IN-GRAPH, while
# host-launched collectives remain a hard failure everywhere.
IN_GRAPH_COLLECTIVES = frozenset({
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all",
})


class StateArena:
    """Declared device-resident serving state with resolved shardings.

    With ``mesh=None`` (the default) every method degenerates to the
    unsharded behaviour the engines had before the arena existed — same
    dtypes, same commitments, same program keys — so single-device legs
    are bit-identical.  With a mesh, declared leaves are placed as
    ``NamedSharding(mesh, spec)`` and program keys/display names gain a
    mesh tag (e.g. ``[mp2]``) so sharded programs never collide with
    unsharded ones in the shared per-model store.
    """

    def __init__(self, mesh=None, shard_rules=None, program_cache_cap=64):
        self.mesh = mesh
        self.shard_rules = (tuple(shard_rules) + tuple(DEFAULT_SHARD_RULES)
                            if shard_rules else DEFAULT_SHARD_RULES)
        self.program_cache_cap = int(program_cache_cap)
        self._state = {}
        self._lru = OrderedDict()   # (id(store), key) -> store
        self._evicted = set()       # lkeys dropped by the LRU cap
        # True once a declared KV pool's head axis actually sharded —
        # drives the pallas shard_map route in decode_paged.
        self.kv_head_axis = False

    # -- mesh introspection ----------------------------------------------
    @property
    def multi_device(self):
        return self.mesh is not None and self.mesh.devices.size > 1

    @property
    def tag(self):
        """Program-key decoration, e.g. ``"[mp2]"``; empty when the mesh
        is absent or trivial so mesh(1,1) arenas key (and therefore
        compile + count) identically to unsharded engines."""
        if not self.multi_device:
            return ""
        inner = "".join(f"{a}{n}" for a, n in self.mesh.shape.items()
                        if n > 1)
        return f"[{inner}]"

    def decorate(self, name):
        return name + self.tag

    @property
    def expected_collectives(self):
        """Allowlist for the program audit: in-graph collectives are
        expected on a multi-device arena, forbidden otherwise."""
        return IN_GRAPH_COLLECTIVES if self.multi_device else None

    # -- spec resolution --------------------------------------------------
    def _degraded(self, msg):
        counters.inc("serving.mesh.spec_degraded")

    def resolve_spec(self, name, spec, shape):
        """Validate ``spec`` against ``shape`` on the arena's mesh,
        soft-degrading to replicated (``serving.mesh.spec_degraded``)
        on indivisible dims or unknown axes."""
        if self.mesh is None:
            return None
        return validate_spec(spec, shape, self.mesh, name=name,
                             on_fallback=self._degraded)

    # -- declaration / binding -------------------------------------------
    def declare(self, name, value, spec=None):
        """Place one array leaf and take ownership of it under ``name``.

        ``spec=None`` (or no mesh) commits the value replicated /
        single-device; otherwise the resolved spec decides placement.
        """
        if value is None:
            self._state[name] = None
            return None
        if self.mesh is None:
            value = jnp.asarray(value)
        else:
            rspec = self.resolve_spec(name, spec, np.shape(value)) or P()
            value = jax.device_put(value, NamedSharding(self.mesh, rspec))
            # only the TARGET pools drive the pallas shard_map route —
            # the draft's head count may shard (or degrade) independently
            if (name in ("pool_k", "pool_v")
                    and any(ax is not None for ax in rspec)):
                self.kv_head_axis = True
        self._state[name] = value
        return value

    def declare_tree(self, name, tree):
        """Place a weight pytree leaf-by-leaf via the arena's shard
        rules (``infer_partition_specs``); pass-through without a mesh."""
        if tree is None:
            self._state[name] = None
            return None
        if self.mesh is None:
            self._state[name] = tree
            return tree
        specs = infer_partition_specs(tree, self.mesh, self.shard_rules,
                                      on_fallback=self._degraded)
        placed = jax.tree_util.tree_map(
            lambda leaf, spec: jax.device_put(
                leaf, NamedSharding(self.mesh, spec if spec is not None
                                    else P())),
            tree, specs)
        self._state[name] = placed
        return placed

    def bind(self, name, value):
        """Rebind a donated-program output (already placed — donation
        preserves the input sharding) without re-placing it."""
        self._state[name] = value
        return value

    def get(self, name):
        return self._state.get(name)

    def operand(self, x):
        """Commit a per-step operand (block tables, positions, sampling
        params) — replicated on the arena's mesh so it never forces a
        resharding transfer inside the dispatched program, and typed like
        the same operand when a program hands it back (the decode
        program's carried tokens, positions and keys: on a mesh, of one
        device too, an output's type names the mesh, and an upload that
        did not would trace the program a second time)."""
        if self.mesh is not None:
            return jax.device_put(x, NamedSharding(self.mesh, P()))
        return jnp.asarray(x)

    # -- accounting -------------------------------------------------------
    def device_bytes(self, *names):
        """Per-chip bytes of the named entries (addressable shard 0),
        i.e. what one chip's HBM actually holds after sharding."""
        total = 0
        for name in names:
            entry = self._state.get(name)
            if entry is None:
                continue
            for leaf in jax.tree_util.tree_leaves(entry):
                shards = getattr(leaf, "addressable_shards", None)
                if shards:
                    total += int(shards[0].data.nbytes)
                elif hasattr(leaf, "nbytes"):
                    total += int(leaf.nbytes)
        return total

    def shard_shape(self, name):
        """Shape of chip 0's shard of ``name`` (the sharded-shape proof
        check_counters asserts on)."""
        entry = self._state.get(name)
        if entry is None:
            return None
        shards = getattr(entry, "addressable_shards", None)
        if shards:
            return tuple(shards[0].data.shape)
        return tuple(entry.shape)

    # -- program cache ----------------------------------------------------
    def program(self, store, key, build):
        """Fetch-or-build a compiled program in the per-model shared
        ``store``, LRU-capped across every store this arena fronts.

        Hits/misses/evictions tick ``serving.arena.program_*``; a key
        rebuilt after eviction additionally ticks ``program_rebuilds``
        (the retrace-accounting signal check_counters watches).
        """
        lkey = (id(store), key)
        fn = store.get(key)
        if fn is not None:
            counters.inc("serving.arena.program_hits")
            self._lru[lkey] = store
            self._lru.move_to_end(lkey)
            return fn
        counters.inc("serving.arena.program_misses")
        if lkey in self._evicted:
            # compiled before, dropped by the cap, needed again: the
            # retrace-accounting signal check_counters watches
            counters.inc("serving.arena.program_rebuilds")
            self._evicted.discard(lkey)
        fn = build()
        store[key] = fn
        self._lru[lkey] = store
        self._lru.move_to_end(lkey)
        while len(self._lru) > self.program_cache_cap:
            (old_store_id, old_key), old_store = self._lru.popitem(last=False)
            if old_store.pop(old_key, None) is not None:
                counters.inc("serving.arena.program_evictions")
                self._evicted.add((old_store_id, old_key))
        counters.set_gauge("serving.arena.programs", len(self._lru))
        return fn
