"""Fleet-gateway tier: radix prefix tree, eviction coherence, routing.

The fleet tier (SERVING.md §8) hinges on one global invariant: the
radix prefix tree may *over*-advertise (a replica listed for a prefix
it has since evicted costs a cache miss) but after the pool's
``evict_callback`` has fired it must never *under*-withdraw (a stale
advertisement surviving eviction would route a request to a replica
that serves garbage). These tests pin that down:

* property suite (hypothesis when available, pinned parametrization
  otherwise) for the tree — insert/match round-trips, longest-prefix
  match vs a brute-force oracle, eviction leaves no dangling replica
  refs and prunes every empty node;
* eviction-coherence regression — a routed request whose advertised
  prefix was LRU-evicted from the replica pool degrades to a prefill
  miss, never a stale-block read;
* router/gateway behaviour — every policy drains the seeded trace,
  prefix routing beats random on global hit rate, the O(requests)
  bookkeeping bound holds, and backpressure respects the dispatch
  window.
"""
import numpy as np
import pytest

try:
    from hypothesis import example, given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:      # property tests degrade to fixed parametrization
    HAVE_HYPOTHESIS = False

from repro.serve.gateway import ROUTERS, FleetGateway, catalogue
from repro.serve.kv_cache import PagedKVPool
from repro.serve.prefix_tree import RadixPrefixTree
from repro.serve.traces import TraceRequest, TraceSpec, generate

BT = 4      # block_tokens used throughout the tree tests


# --- brute-force oracle -------------------------------------------------------

class OracleTree:
    """Reference model: a dict of advertised (replica, prefix-run) pairs,
    no sharing, no pruning — O(everything), obviously correct."""

    def __init__(self, block_tokens: int):
        self.bt = block_tokens
        self.runs: set = set()      # (replica, blocks-tuple prefix chain)

    def _blocks(self, tokens):
        toks = list(tokens)
        return tuple(tuple(toks[j * self.bt:(j + 1) * self.bt])
                     for j in range(len(toks) // self.bt))

    def insert(self, tokens, replica):
        blocks = self._blocks(tokens)
        for k in range(1, len(blocks) + 1):
            self.runs.add((replica, blocks[:k]))

    def match(self, tokens) -> dict:
        blocks = self._blocks(tokens)
        out = {}
        for rep, run in self.runs:
            if run == blocks[:len(run)]:
                out[rep] = max(out.get(rep, 0), len(run))
        return out

    def evict_prefix(self, tokens, depth, replica):
        """Withdraw ``replica`` from the depth-``depth`` prefix of
        ``tokens`` and everything below it (mirror of subtree evict)."""
        victim = self._blocks(tokens)[:depth]
        self.runs = {(rep, run) for rep, run in self.runs
                     if not (rep == replica and run[:depth] == victim
                             and len(run) >= depth)}


def _apply_ops(ops):
    """Drive tree + oracle through an op list; cross-check after every
    step. Ops: ('insert', tokens, replica) | ('evict', tokens, depth,
    replica) | ('match', tokens). An eviction names the node the tree
    last gave the depth-``depth`` prefix of ``tokens`` (a pruned prefix
    comes back under a new id; its old id is unknown to the tree)."""
    tree = RadixPrefixTree(BT)
    oracle = OracleTree(BT)
    inserted: set = set()       # tokens-tuples inserted so far
    ids: dict = {}              # token-prefix tuple -> its latest node id
    for op in ops:
        if op[0] == "insert":
            _, tokens, rep = op
            for k, nid in enumerate(tree.insert(tokens, rep), 1):
                ids[tuple(tokens[:k * BT])] = nid
            inserted.add(tuple(tokens))
            oracle.insert(tokens, rep)
        elif op[0] == "evict":
            _, tokens, depth, rep = op
            nid = ids.get(tuple(tokens[:depth * BT]))
            if depth * BT <= len(tokens) and nid is not None:
                tree.evict(nid, rep)
                oracle.evict_prefix(tokens, depth, rep)
        tree.check()
        for probe_tokens in inserted | {tuple(op[1])}:
            assert tree.match(list(probe_tokens)) == \
                oracle.match(probe_tokens), (op, probe_tokens)
    return tree


def _gen_ops(rng, n_ops):
    """Random op list over a tiny alphabet so prefixes collide often."""
    ops = []
    pool = [list(rng.integers(0, 3, size=int(rng.integers(0, 5)) * BT))
            for _ in range(6)]
    for _ in range(n_ops):
        tokens = pool[int(rng.integers(len(pool)))]
        rep = int(rng.integers(0, 3))
        if rng.random() < 0.6 or not ops:
            ops.append(("insert", tokens, rep))
        else:
            depth = int(rng.integers(1, max(len(tokens) // BT, 1) + 1))
            ops.append(("evict", tokens, depth, rep))
    return ops


if HAVE_HYPOTHESIS:

    @settings(max_examples=60, deadline=None)
    @given(st.integers(0, 2**32 - 1), st.integers(1, 40))
    # a prefix pruned, then interned anew by a longer insert, then evicted
    @example(1_019_738_452, 27)
    def test_tree_matches_oracle(seed, n_ops):
        _apply_ops(_gen_ops(np.random.default_rng(seed), n_ops))

else:

    @pytest.mark.parametrize("seed", range(40))
    def test_tree_matches_oracle(seed):
        _apply_ops(_gen_ops(np.random.default_rng(seed), 40))


def test_tree_insert_match_roundtrip():
    tree = RadixPrefixTree(BT)
    toks = list(range(3 * BT))
    ids = tree.insert(toks, replica=1)
    assert len(ids) == 3
    assert tree.match(toks) == {1: 3}
    # partial trailing block is never indexed
    assert tree.insert(list(range(BT + 2)), replica=2) == [ids[0]]
    assert tree.match(toks) == {1: 3, 2: 1}
    # chain ids are stable across re-insertion (content addressing)
    assert tree.insert(toks, replica=1) == ids
    tree.check()


def test_tree_match_requires_contiguous_run():
    """A replica holding blocks 0 and 2 but not 1 matches depth 1: a
    prefix run must be contiguous from the root."""
    tree = RadixPrefixTree(BT)
    toks = list(range(3 * BT))
    ids = tree.insert(toks, replica=0)
    tree.insert(toks, replica=1)        # keeps the chain alive
    tree.evict(ids[1], replica=0)       # 0 loses block 1 (and 2: subtree)
    assert tree.match(toks) == {0: 1, 1: 3}


def test_tree_evict_prunes_and_leaves_no_dangling_refs():
    tree = RadixPrefixTree(BT)
    toks = list(range(4 * BT))
    ids = tree.insert(toks, replica=0)
    assert tree.n_nodes == 4
    # evicting the root block withdraws the whole chain and prunes it
    assert tree.evict(ids[0], replica=0)
    assert tree.n_nodes == 0
    assert tree.match(toks) == {}
    tree.check()
    # idempotent: the node ids are gone, a second evict is a no-op
    assert not tree.evict(ids[0], replica=0)
    # unknown / non-tree keys (pool decode-churn) are ignored
    assert not tree.evict(("decode", 7), replica=0)


def test_tree_evict_keeps_other_replicas():
    tree = RadixPrefixTree(BT)
    toks = list(range(2 * BT))
    ids = tree.insert(toks, replica=0)
    tree.insert(toks, replica=1)
    tree.evict(ids[0], replica=0)
    assert tree.match(toks) == {1: 2}
    assert tree.n_nodes == 2            # still live for replica 1
    tree.drop_replica(1)
    assert tree.n_nodes == 0
    tree.check()


# --- eviction coherence (pool <-> tree) ---------------------------------------

def test_pool_evict_callback_fires_on_lru_eviction():
    dropped = []
    pool = PagedKVPool(4, evict_callback=dropped.append)
    pool.insert("a", 2)
    pool.insert("b", 2)
    pool.insert("c", 1)                 # evicts the LRU "a" block
    assert dropped == [("a", 0)]
    pool.check()


def test_evicted_prefix_degrades_to_miss_never_stale():
    """The regression the fleet tier exists to prevent: replica 0's pool
    LRU-evicts a tenant prefix; the tree withdraws the advertisement; a
    request routed afterwards sees a prefill MISS on that replica (and
    the router no longer prefers it) — never a hit against blocks the
    pool has dropped."""
    tree = RadixPrefixTree(BT)
    pool = PagedKVPool(4, evict_callback=lambda k: tree.evict(k[0], 0))
    toks = list(range(2 * BT))
    chain = tree.insert(toks, replica=0)
    for nid in chain:
        pool.insert(nid, 1)
    assert tree.match(toks) == {0: 2}
    assert all(pool.hit_fraction(nid, 1) == 1.0 for nid in chain)
    # unrelated churn forces LRU eviction of the tenant's root block
    pool.insert("churn1", 2)
    pool.insert("churn2", 2)
    # coherence: the tree withdrew replica 0 the moment the pool dropped
    # the block — the router will not prefer replica 0 for this tenant
    assert tree.match(toks) == {}
    # and an already-routed request probing the old chain sees a miss
    assert pool.hit_fraction(chain[0], 1) == 0.0
    pool.check()


def test_gateway_advertised_then_evicted_prefix_is_clean_miss():
    """End-to-end: run a trace that overflows the (tiny) per-replica
    pools. Every prefill hit the executors count must be backed by
    pool-resident blocks at admission time; total hit rate stays below
    1 and the run completes (stale reads would surface as hits after
    the tree withdrew the replica, or as pool.check() violations)."""
    gw = FleetGateway(n_replicas=2, router="prefix", max_slots=4,
                      pool_blocks=24, block_tokens=16, seed=0)
    s = gw.run(generate(TraceSpec(n_requests=300, n_tenants=40, seed=5)))
    assert s["n"] == 300
    assert 0.0 <= s["hit_rate"] < 1.0
    for rep in gw.replicas:
        rep.pool.check()
    gw.tree.check()
    # the tree only advertises what eviction has not withdrawn: every
    # advertised node id must still be a live tree node
    assert gw.tree.stats.evictions > 0      # the pools really churned


# --- routers / gateway --------------------------------------------------------

def _tiny_trace(n=240, seed=11):
    return generate(TraceSpec(n_requests=n, n_tenants=24, seed=seed))


@pytest.mark.parametrize("router", sorted(ROUTERS))
def test_every_router_drains_the_trace(router):
    gw = FleetGateway(n_replicas=3, router=router, max_slots=4,
                      pool_blocks=64, seed=2)
    s = gw.run(_tiny_trace())
    assert s["n"] == 240
    assert s["router"] == router
    assert s["goodput_tok_per_step"] > 0
    assert s["load_imbalance"] >= 1.0
    # every request was dispatched somewhere real
    assert sum(gw.stats.per_replica) == 240


def test_unknown_router_rejected():
    with pytest.raises(ValueError, match="unknown router"):
        FleetGateway(router="nope")


def test_catalogue_matches_registry():
    assert [name for name, _ in catalogue()] == list(ROUTERS)


def test_bookkeeping_is_linear_in_requests():
    """The satellite micro-assert, unit-sized: one heap pop + one
    retirement per request, independent of trace length or policy."""
    for n in (60, 240):
        gw = FleetGateway(n_replicas=2, router="round_robin", max_slots=4,
                          pool_blocks=64, seed=3)
        gw.run(_tiny_trace(n=n))
        assert sum(r.core.bookkeeping_ops for r in gw.replicas) == 2 * n


def test_prefix_routing_beats_random_on_hit_rate():
    """The suite's headline claim at unit scale, same seeds both sides."""
    def run(router):
        gw = FleetGateway(n_replicas=4, router=router, max_slots=8,
                          pool_blocks=96, seed=1)
        return gw.run(generate(TraceSpec(n_requests=2000, n_tenants=80,
                                         seed=9)))
    assert run("prefix")["hit_rate"] > run("random")["hit_rate"]


def test_dispatch_window_backpressure():
    """The router never overfills a replica: backlog stays within the
    dispatch window while the router still holds queued requests."""
    gw = FleetGateway(n_replicas=2, router="least_loaded", max_slots=2,
                      pool_blocks=64, queue_depth=2, seed=4)
    # a single burst far bigger than the fleet's total window
    reqs = [TraceRequest(rid=i, arrival=1.0, tenant=0,
                         tokens=np.arange(32, dtype=np.int32),
                         prompt_tokens=32, shared_tokens=32,
                         decode_tokens=8)
            for i in range(40)]
    for r in reqs:
        gw.router.submit(r)
    for _ in range(3):
        gw.step()
        for rep in gw.replicas:
            assert rep.core.backlog <= gw.window
    assert len(gw.router) > 0           # backpressure actually engaged
    while gw.has_work():
        gw.step()
    assert gw.stats.n == 40


def test_trace_generator_is_sorted_seeded_and_bounded():
    spec = TraceSpec(n_requests=500, seed=21)
    a = list(generate(spec))
    b = list(generate(TraceSpec(n_requests=500, seed=21)))
    assert len(a) == 500
    arr = [r.arrival for r in a]
    assert arr == sorted(arr)
    assert [r.rid for r in a] == [r.rid for r in b]
    assert [r.tenant for r in a] == [r.tenant for r in b]
    assert all(r.decode_tokens >= 1 for r in a)
    lo, hi = spec.shared_blocks
    for r in a[:50]:
        assert lo * spec.block_tokens <= r.shared_tokens \
            <= hi * spec.block_tokens
        assert r.prompt_tokens == len(r.tokens)
        # shared prefix really is the tenant system prompt
        same = [q for q in a[:50] if q.tenant == r.tenant]
        for q in same:
            n = min(r.shared_tokens, q.shared_tokens)
            assert np.array_equal(r.tokens[:n], q.tokens[:n])
