"""Pure units for models/prefix_tree.py (ISSUE 20) — the radix index
behind the serving prefix cache and the fleet's prefix-aware routing.
Deliberately jax-free: rows are plain RowRef payloads, so these tests
pin the tree's invariants (split inheritance, refcounted byte
accounting, LRU + path compression, fingerprint chaining) without
touching the model stack."""

import os

import pytest

from parameter_server_distributed_tpu.models.prefix_tree import (
    PrefixTree, RowRef, block_hashes, fp_block, overlap_blocks, pack_fp,
    unpack_fp)


def ref(nbytes=100):
    return RowRef(row=object(), nbytes=nbytes)


def test_lookup_matches_partially_into_edge():
    t = PrefixTree(10**9)
    t.insert((1, 2, 3, 4, 5), last="L", handle=ref())
    node, matched, partial = t.lookup((1, 2, 3, 9))
    assert matched == 3 and partial
    # the partially-entered child's handle covers the matched prefix
    assert node.handle is not None and node.depth == 5
    node, matched, partial = t.lookup((7, 7))
    assert matched == 0 and not partial and node is t.root


def test_split_inherits_handle_and_counts_bytes_once():
    t = PrefixTree(10**9)
    r1 = ref(100)
    t.insert((1, 2, 3, 4, 5), last="a", handle=r1)
    assert t.bytes == 100 and t.nodes == 1
    r2 = ref(150)
    t.insert((1, 2, 3, 9, 9), last="b", handle=r2)
    # split at depth 3: interior node SHARES r1 (no copy, no recharge)
    assert t.splits == 1 and t.nodes == 3
    assert t.bytes == 250  # 100 once (refs=2) + 150
    assert r1.refs == 2 and r2.refs == 1
    mid, matched, partial = t.lookup((1, 2, 3))
    assert matched == 3 and not partial
    assert mid.handle is r1 and mid.last is None  # interior, no logits


def test_readmission_fills_last_and_draft_handle():
    t = PrefixTree(10**9)
    t.insert((1, 2, 3, 4), last="a", handle=ref())
    t.insert((1, 2), last="b", handle=ref(30))  # splits; mid gets last
    mid, matched, partial = t.lookup((1, 2))
    assert not partial and mid.last == "b"
    # the mid node inherited the descendant's handle, so the offered
    # 30-byte handle is NOT taken (and not charged)
    assert t.bytes == 100
    d = ref(40)
    t.insert((1, 2), last="b2", handle=ref(5), dhandle=d)
    assert mid.dhandle is d and t.bytes == 140  # draft row attaches


def test_eviction_is_min_tick_leaf_with_path_compression():
    t = PrefixTree(10**9)
    t.insert((1, 2, 3, 4), last="a", handle=ref())
    t.insert((1, 2, 8, 8), last="b", handle=ref())  # split at (1,2)
    t.insert((5, 5), last="c", handle=ref())
    hit, _, _ = t.lookup((1, 2, 3, 4))
    t.touch(hit)                       # a (and its path) is hot
    hit, _, _ = t.lookup((5, 5))
    t.touch(hit)                       # c is hot; b is the LRU victim
    t.budget_bytes = t.bytes - 1       # force one eviction round
    assert t.evict_over_budget() == 1
    node, matched, _ = t.lookup((1, 2, 8, 8))
    assert matched == 2                # b is gone
    # the split-created (1,2) interior had one child left and no last:
    # path compression merged it away
    node, matched, partial = t.lookup((1, 2, 3, 4))
    assert matched == 4 and not partial and node.last == "a"
    assert node.parent is t.root and node.edge == (1, 2, 3, 4)


def test_ancestor_touch_protects_shared_prefix():
    t = PrefixTree(10**9)
    t.insert((1, 2), last="shared", handle=ref())
    t.insert((9, 9), last="cold", handle=ref())
    deep = t.insert((1, 2, 3, 4), last="deep", handle=ref())
    t.touch(deep)  # touching the descendant refreshes the ancestors
    shared, _, _ = t.lookup((1, 2))
    cold, _, _ = t.lookup((9, 9))
    assert shared.tick > cold.tick
    t.budget_bytes = t.bytes - 1
    t.evict_over_budget()
    _, matched, _ = t.lookup((9, 9))
    assert matched == 0                 # the cold entry was the victim
    node, matched, _ = t.lookup((1, 2))
    assert matched == 2 and node.last == "shared"


def test_evict_over_budget_enforces_byte_bound():
    t = PrefixTree(250)
    for i in range(5):
        t.insert((i, i + 1, i + 2), last=i, handle=ref(100))
    assert t.evict_over_budget() == 3
    assert t.bytes <= 250 and t.nodes == 2 and t.evictions == 3
    # the two survivors are the two most recently admitted
    assert {n.last for n in t._walk()} == {3, 4}


def test_refcounts_drop_bytes_only_at_zero():
    t = PrefixTree(10**9)
    r = ref(100)
    t.insert((1, 2, 3, 4), last="a", handle=r)
    t.insert((1, 2, 7, 7), last="b", handle=ref(60))  # mid shares r
    assert r.refs == 2 and t.bytes == 160
    t.insert((1, 2), last="mid", handle=ref(5))  # complete-prompt mid
    # mid already inherited r, so the 5-byte handle is declined
    assert t.bytes == 160
    # evict the deep leaf: r drops to one ref (the mid node), its 100
    # bytes stay charged — and mid survives (last set, no compression)
    leaf, _, _ = t.lookup((1, 2, 3, 4))
    t._remove_leaf(leaf)
    assert r.refs == 1 and t.bytes == 160
    node, matched, partial = t.lookup((1, 2))
    assert matched == 2 and not partial and node.last == "mid"


def test_compression_sheds_inherited_handle():
    t = PrefixTree(10**9)
    r = ref(100)
    t.insert((1, 2, 3, 4), last="a", handle=r)
    t.insert((1, 2, 7, 7), last="b", handle=ref(60))
    # removing the leaf that brought r leaves the split node with one
    # child and no complete-prompt payload: it merges away and releases
    # its inherited reference — r hits zero refs and is uncharged
    leaf, _, _ = t.lookup((1, 2, 3, 4))
    t._remove_leaf(leaf)
    assert r.refs == 0 and t.bytes == 60
    node, matched, partial = t.lookup((1, 2, 7, 7))
    assert matched == 4 and not partial and node.edge == (1, 2, 7, 7)


def test_clear_resets_everything():
    t = PrefixTree(10**9)
    t.insert((1, 2, 3), last="a", handle=ref())
    assert t.fingerprint == b"" or t.nodes  # fp may be empty (short path)
    t.insert(tuple(range(40)), last="b", handle=ref())
    assert t.fingerprint != b""
    t.clear()
    assert t.nodes == 0 and t.bytes == 0 and t.fingerprint == b""
    assert not t.root.children


def test_fingerprint_matches_router_block_hashes(monkeypatch):
    monkeypatch.setenv("PSDT_PREFIX_FP_BLOCK", "4")
    t = PrefixTree(10**9)
    prompt = tuple(range(10))
    t.insert(prompt, last="a", handle=ref())
    fp = unpack_fp(t.fingerprint)
    hashes = block_hashes(prompt)
    assert len(hashes) == 2            # boundaries at 4 and 8 of 10
    assert overlap_blocks(hashes, fp) == 2
    # a prompt diverging inside the first block shares nothing
    other = (99,) + prompt[1:]
    assert overlap_blocks(block_hashes(other), fp) == 0
    # consecutive-from-start: a hole ends the reusable prefix
    assert overlap_blocks([hashes[0], 0xDEAD, hashes[1]], fp) == 1


def test_fingerprint_cap_keeps_shallow_blocks(monkeypatch):
    monkeypatch.setenv("PSDT_PREFIX_FP_BLOCK", "2")
    monkeypatch.setenv("PSDT_PREFIX_FP_MAX", "3")
    t = PrefixTree(10**9)
    t.insert(tuple(range(20)), last="a", handle=ref())
    fp = unpack_fp(t.fingerprint)
    assert len(fp) == 3
    # the SHALLOW boundaries survive the cap (BFS): blocks 1..3, not the
    # deep tail — exactly the shared-system-prompt blocks routing needs
    assert overlap_blocks(block_hashes(tuple(range(20))), fp) == 3


def test_pack_unpack_roundtrip_and_truncation():
    hashes = [0, 1, 0xFFFFFFFF, 12345]
    blob = pack_fp(hashes)
    assert len(blob) == 16
    assert unpack_fp(blob) == frozenset(hashes)
    # a truncated tail from a foreign writer is ignored, not misparsed
    assert unpack_fp(blob[:-2]) == frozenset(hashes[:3])
    assert unpack_fp(b"") == frozenset()


def test_fp_block_env_default():
    assert "PSDT_PREFIX_FP_BLOCK" not in os.environ or True
    assert fp_block() >= 1


def test_a_tail_as_wide_as_its_document_goes_before_the_document():
    """Four shared documents, then a stream of one-token turns under
    three of them, each pinning a row as wide as its document: the tails
    evict each other and every document stays, the one that is never
    used too, and the one admitted LAST and not yet used while the
    others' tails fill the budget (a warm-up's order).  Plain LRU evicted
    a document as soon as the rows admitted since its last use filled the
    budget."""
    t = PrefixTree(1000)
    docs = [tuple([d] * 50) for d in range(4)]
    for doc in docs:
        t.insert(doc, last="l", handle=ref(100))
    for i in range(40):                   # all under documents 0-2
        doc = docs[i % 3]
        node, matched, _ = t.lookup(doc + (100 + i,))
        assert matched == 50
        t.touch(node)
        leaf = t.insert(doc + (100 + i,), last="l", handle=ref(102))
        assert leaf.is_tail and not node.is_tail
        t.evict_over_budget()
        assert t.bytes <= 1000
    for doc in docs:
        node, matched, partial = t.lookup(doc)
        assert matched == 50 and not partial and node.handle is not None
    assert t.evictions == 35              # 5 tails fit beside the docs
    # a turn that is more than an eighth of its path is no tail: among
    # such rows, and once no tail is left, it is plain LRU
    long_turn = docs[0] + tuple(range(200, 208))
    assert not t.insert(long_turn, last="l", handle=ref(102)).is_tail
    t.budget_bytes = 350
    t.evict_over_budget()
    assert t.lookup(docs[3])[1] == 0 and t.lookup(long_turn)[1] == 58


# ------------------------------------------- rows that carry a snapshot
@pytest.mark.parametrize("snapshots", [False, True])
def test_the_tree_matches_only_where_a_snapshot_lies(snapshots):
    """A split inherits the K/V handle and not the snapshot; a tree
    without snapshots matches into the edge as ever."""
    tree = PrefixTree(1 << 20, snapshots=snapshots)

    def row(tokens):
        return RowRef(("row", len(tokens)), 100,
                      state_at=len(tokens) if snapshots else None)

    document = tuple(range(10))
    tree.insert(document, "d", row(document))
    first = document + (20, 21, 22, 23)
    tree.insert(first, "a", row(first))
    second = document + (20, 21, 30)
    node, matched, partial = tree.lookup(second)
    assert (matched, partial) == ((10, False) if snapshots else (12, True))
    tree.insert(second, "b", row(second))
    assert tree.splits == 1
    split, matched, _ = tree.lookup(document + (20, 21, 99))
    assert matched == (10 if snapshots else 12)
    assert split.handle.row == (("row", 10) if snapshots else ("row", 14))
    # the split node admitted as a prompt of its own takes its own row
    own = document + (20, 21)
    tree.insert(own, "c", row(own))
    node, matched, partial = tree.lookup(own + (5,))
    assert (matched, partial) == (12, False)
    assert node.handle.row == (("row", 12) if snapshots else ("row", 14))
    assert tree.bytes == (400 if snapshots else 300)


def test_eviction_counts_a_rows_snapshot_and_keeps_the_documents():
    """A row's bytes are the caller's count, snapshot included; a tail
    under a resident document goes first, and a lookup then falls back to
    the document's own snapshot."""
    tree = PrefixTree(1000, snapshots=True)
    document = tuple(range(64))
    tree.insert(document, "d", RowRef("doc", 400 + 50, state_at=64))
    for turn in (100, 101):
        prompt = document + (turn, 7)
        tree.insert(prompt, "t", RowRef("turn", 420 + 50, state_at=66))
        tree.evict_over_budget()
    assert tree.bytes == 920 and tree.evictions == 1
    node, matched, partial = tree.lookup(document + (100, 7))
    assert (node.handle.row, matched, partial) == ("doc", 64, False)
    node, matched, _ = tree.lookup(document + (101, 7, 9))
    assert (node.handle.row, matched) == ("turn", 66)


@pytest.mark.parametrize("used", [False, True])
def test_a_row_nothing_started_from_goes_before_one_that_was(used):
    """Two contexts and the second's long turns (no tails), a budget for
    four rows: the first context is the least recently touched leaf, and it
    stays where an admission has started from it (:meth:`PrefixTree.use`)
    and no other leaf can say that; unused, it goes as the oldest."""
    tree = PrefixTree(400)
    first, second = tuple(range(100, 116)), tuple(range(200, 216))
    tree.insert(first, "a", RowRef("first", 100))
    tree.insert(second, "b", RowRef("second", 100))
    if used:
        tree.use(tree.lookup(first)[0])
    for turn in range(3):
        tree.insert(second + tuple(range(300 + 10 * turn, 308 + 10 * turn)),
                    "t", RowRef(("turn", turn), 100))
        tree.evict_over_budget()
    assert tree.bytes == 400 and tree.evictions == 1
    node, matched, _ = tree.lookup(first + (1,))
    assert matched == (16 if used else 0)
    assert (tree.lookup(second + tuple(range(300, 308)))[1] == 24) != used


def test_shared_counts_what_a_path_holds_snapshot_or_not():
    tree = PrefixTree(1000, snapshots=True)
    path = tuple(range(40))
    tree.insert(path, "p", RowRef("row", 100, state_at=40))
    other = path[:25] + (99, 98)
    assert tree.shared(other) == 25 and tree.lookup(other)[1] == 0
    # the shared run admitted as a prompt of its own: a node with a
    # snapshot where the split node had none
    tree.insert(path[:25], "s", RowRef("shared", 60, state_at=25))
    node, matched, partial = tree.lookup(other)
    assert (node.handle.row, matched, partial) == ("shared", 25, False)
    assert tree.shared(path + (7,)) == 40 and tree.shared((5,)) == 0


def test_rows_asked_for_again_hold_a_share_of_the_budget_and_no_more():
    """Three prompts each replayed once, a budget of four rows: the rows
    admissions started from may hold half of it, so the oldest of the
    three stands among the unused again and goes before the rows that came
    after it, asked for again or not (with every row asked for again the
    store is the plain LRU)."""
    from parameter_server_distributed_tpu.models import prefix_tree

    assert prefix_tree.PROTECTED_SHARE == 0.5
    tree = PrefixTree(400)
    prompts = [tuple(range(100 * i, 100 * i + 16)) for i in range(1, 6)]
    for prompt in prompts[:3]:
        tree.insert(prompt, "x", RowRef(prompt[0], 100))
        tree.use(tree.lookup(prompt)[0])
    for prompt in prompts[3:]:
        tree.insert(prompt, "x", RowRef(prompt[0], 100))
        tree.evict_over_budget()
    held = [tree.lookup(prompt)[1] == 16 for prompt in prompts]
    assert held == [False, True, True, True, True] and tree.evictions == 1
    assert [tree.lookup(p)[0].uses for p in prompts[1:3]] == [1, 1]
