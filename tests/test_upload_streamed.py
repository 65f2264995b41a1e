"""A pulled store is uploaded while it lands (ISSUE 53).

The trainer's flat input is cut into sections of at most the bucket
budget; what ``Trainer.lend_store`` lends puts a section on the device the
moment every element of it has landed and, when the last one of a shard is
on its way, joins them on the device into the array the step takes;
``Trainer._dispatch_step`` takes a shard uploaded that way where the store
still lies in that buffer and uploads every other one itself.  Held here: the step computes the same bits either way
over three rounds on both buffers; a section goes up only when it is whole;
a failed attempt's sections never reach a step and a retry's do; whatever
was not landed through the loan goes up at dispatch and is counted in
``worker.upload.bytes`` only; a four-device mesh cuts every device's shard where it lies;
and a recorded step waits for its upload once.
"""

import jax
import numpy as np
import pytest

from parameter_server_distributed_tpu.obs import stats as obs_stats
from parameter_server_distributed_tpu.obs import trace as obs_trace
from parameter_server_distributed_tpu.rpc import messages as m
from parameter_server_distributed_tpu.worker import trainer as trainer_mod
from parameter_server_distributed_tpu.worker.trainer import Trainer

from test_pull_landing import (SHAPES, _Model, _batch, _bits, _land, _served,
                               _sgd, _store, _wired, _worker)

# sections of ten elements: emb/w (42) spans five, the section [40, 50)
# holds the end of emb/w, head/b (6) and the start of layer/w, and the
# store's 85 elements end in the middle of the ninth
BUDGET = 40
PACKED = sum(int(np.prod(s)) for s in SHAPES.values())

_uploaded = obs_stats.counter("worker.upload.bytes")
_streamed = obs_stats.counter("worker.upload.streamed_bytes")


@pytest.fixture(autouse=True)
def small_sections(monkeypatch):
    monkeypatch.setenv("PSDT_BUCKET_BYTES", str(BUDGET))


def _counted(fn):
    """``fn()`` beside how far the two upload counters moved."""
    before = _uploaded.value, _streamed.value
    result = fn()
    return result, _uploaded.value - before[0], _streamed.value - before[1]


def _put_log(trainer):
    """Every section the trainer puts on the device as it lands, from now
    on, as ``(index, a copy of the bytes it was put with)``."""
    puts, put = [], trainer._put_landed

    def logged(flat, s):
        a, b = trainer._cuts[s]
        puts.append((s, flat[a:b].copy()))
        return put(flat, s)

    trainer._put_landed = logged
    return puts


def _on_device(loan):
    """ids of the device arrays a loan holds: sections and joined shards."""
    return {id(a) for parts in loan._parts if parts for a in parts.values()
            } | {id(a) for a in loan._whole.values()}


def test_the_cut_follows_the_budget_and_covers_the_buffer():
    trainer = Trainer(_Model())
    assert trainer._padded_in == PACKED == 85
    assert trainer._cuts == [(a, min(a + 10, 85)) for a in range(0, 85, 10)]
    # no budget: the whole buffer is one section
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("PSDT_BUCKET_BYTES", "0")
        assert Trainer(_Model())._cuts == [(0, 85)]


def test_sections_uploaded_early_give_the_bits_of_an_upload_at_dispatch():
    """Three rounds, so both buffers are landed in and one twice: the step
    fed stores whose sections went up as they landed and the step fed
    private copies of the same stores (all of it uploaded at dispatch)
    return the same flat output to the bit."""
    early, late = Trainer(_Model()), Trainer(_Model())
    worker = _worker(early)
    store = _store(60)
    at = []
    for r in range(3):
        landed = _land(worker, _served(store))
        at.append(landed["emb/w"].__array_interface__["data"][0])
        (out, _), uploaded, streamed = _counted(
            lambda: early._dispatch_step(landed, _batch(r)))
        assert uploaded == streamed == 4 * early._padded_in
        (want, _), uploaded, streamed = _counted(
            lambda: late._dispatch_step(
                {n: a.copy() for n, a in store.items()}, _batch(r)))
        assert (uploaded, streamed) == (4 * late._padded_in, 0)
        got, want = np.asarray(out), np.asarray(want)
        assert got.tobytes() == want.tobytes() and np.isfinite(got).all()
        grads = {name: want[1 + off:1 + off + size].reshape(shape)
                 for name, off, size, shape, _ in late._layout}
        store = _sgd(store, grads)
        del landed, out
    assert at[0] != at[1] and at[2] == at[0]


def test_a_section_goes_up_only_when_every_element_of_it_has_landed():
    """One tensor at a time, largest name last: after each the sections
    put so far are exactly those that lie inside what has landed, each put
    once and with the bytes the store has there."""
    trainer = Trainer(_Model())
    worker = _worker(trainer)
    puts = _put_log(trainer)
    store = _store(11)
    flat_want = np.concatenate([store[n].ravel() for n in sorted(store)])
    convert = worker._chunk_converter({})
    order = ["scale", "layer/w", "head/b", "emb/w"]
    landed = np.zeros(PACKED, bool)
    where = {name: (off, size) for name, off, size, *_ in trainer._layout}
    for name in order:
        chunk, = _served({name: store[name]}, chunks=1)
        convert(chunk)
        off, size = where[name]
        landed[off:off + size] = True
        whole = {s for s, (a, b) in enumerate(trainer._cuts)
                 if landed[a:b].all()}
        assert {s for s, _ in puts} == whole
    # layer/w closed three sections of its own and, scale being there,
    # the last; head/b closed none ([40, 50) begins with the end of
    # emb/w); emb/w closed its four and then that one
    assert [s for s, _ in puts] == [5, 6, 7, 8, 0, 1, 2, 3, 4]
    for s, put_bytes in puts:
        a, b = trainer._cuts[s]
        assert put_bytes.tobytes() == flat_want[a:b].tobytes()


def test_a_failed_attempts_sections_never_reach_a_step_and_a_retrys_do():
    trainer = Trainer(_Model())
    worker = _worker(trainer)
    failed_store, store = _store(30), _store(31)
    first = {}
    failed = worker._chunk_converter(first)
    head, tail = _served(failed_store)
    failed(head)                        # ... and the attempt fails here
    failed_loan = trainer._loans[trainer._pack_turn]
    assert len(failed_loan._parts[0]) == 8    # part of it is on the device
    retried = _land(worker, _served(store))
    failed(tail)                        # the straggler lands the rest
    theirs, mine = _on_device(failed_loan), _on_device(
        trainer._loans[trainer._pack_turn])
    assert len(theirs) == len(mine) == 1 and theirs != mine   # both joined
    taken = []
    step = trainer._step
    trainer._step = lambda flat, batch: (taken.append(flat),
                                         step(flat, batch))[1]
    (out, _), uploaded, streamed = _counted(
        lambda: trainer._dispatch_step(retried, _batch(0)))
    assert uploaded == streamed == 4 * trainer._padded_in
    assert {id(a) for a in taken} == mine
    want, _ = Trainer(_Model())._dispatch_step(
        {n: a.copy() for n, a in store.items()}, _batch(0))
    assert np.asarray(out).tobytes() == np.asarray(want).tobytes()
    assert trainer._loans == [None, None]


@pytest.mark.parametrize("case", ["made_elsewhere", "partly_in_place",
                                  "through_to_array", "loan_nobody_used",
                                  "landed_twice", "uploaded_once_already"])
def test_what_was_not_landed_in_place_goes_up_at_dispatch(case):
    """Each case against a trainer given a private copy: the same output to
    the bit, and the whole input counted in ``worker.upload.bytes`` only:
    a shard goes up early whole or not at all."""
    trainer, reference = Trainer(_Model()), Trainer(_Model())
    worker = _worker(trainer)
    store = _store(70)
    if case == "made_elsewhere":
        held = _land(worker, _served(_store(71)))      # the worker's pull
        params = store                                  # round_checks'
    elif case == "partly_in_place":
        params = _land(worker, _served(store))
        store = dict(store, **{"head/b": _store(72)["head/b"]})
        params["head/b"] = store["head/b"]
        # head/b, six elements of 85, is written at dispatch
    elif case == "through_to_array":
        # a name of another size is not landed: to_array's array
        odd = dict(store, **{"layer/w": np.arange(5, dtype=np.float32)})
        params = _land(worker, _served(odd))
        assert params["layer/w"].flags.writeable
        params["layer/w"] = store["layer/w"]
        # layer/w is [48, 84): sections 4 to 8 were never whole
        assert len(trainer._loans[trainer._pack_turn]._parts[0]) == 4
    elif case == "loan_nobody_used":
        trainer.lend_store()
        params = store
    elif case == "landed_twice":
        # a stream that fell back to the unary call inside one attempt
        local = {}
        convert = worker._chunk_converter(local)
        head = _served(_store(73), chunks=5)[0]     # emb/w alone
        convert(head)
        for chunk in _served(store, chunks=1):
            convert(chunk)
        params = local
    else:
        params = _land(worker, _served(store))
        trainer._dispatch_step(params, _batch(9))
    (out, _), uploaded, streamed = _counted(
        lambda: trainer._dispatch_step(params, _batch(1)))
    assert (uploaded, streamed) == (4 * trainer._padded_in, 0)
    want, _ = reference._dispatch_step(
        {n: np.array(a) for n, a in store.items()}, _batch(1))
    assert np.asarray(out).tobytes() == np.asarray(want).tobytes()
    assert trainer._loans == [None, None]     # nothing of another store stays
    if case == "made_elsewhere":
        assert _bits(held) == _wired(_store(71))


def test_what_has_landed_cannot_be_written_behind_the_uploads_back():
    """The landed store is read-only (its sections are on the device);
    the loan's own views, the converter's destination, stay writable."""
    trainer = Trainer(_Model())
    landed = _land(_worker(trainer), _served(_store(5)))
    assert not any(a.flags.writeable for a in landed.values() if a.size)
    with pytest.raises(ValueError):
        landed["emb/w"][0, 0] = 1.0
    assert all(a.flags.writeable for a in trainer.lend_store().values())


@pytest.mark.parametrize("wire", ["bf16", "float64"])
def test_a_wire_that_had_to_be_unpacked_still_goes_up_as_it_lands(wire):
    store = _store(3)
    if wire == "float64":
        store = {n: a.astype(np.float64) for n, a in store.items()}
    wire_dtype = m.WIRE_DTYPE_NAMES.get(wire, m.WIRE_F32)
    trainer, reference = Trainer(_Model()), Trainer(_Model())
    landed = _land(_worker(trainer), _served(store, wire_dtype))
    (out, _), uploaded, streamed = _counted(
        lambda: trainer._dispatch_step(landed, _batch(2)))
    assert uploaded == streamed == 4 * trainer._padded_in
    want, _ = reference._dispatch_step(
        {n: np.array(a) for n, a in landed.items()}, _batch(2))
    assert np.asarray(out).tobytes() == np.asarray(want).tobytes()


def test_a_four_device_mesh_cuts_every_devices_shard_where_it_lies():
    """On a worker mesh the flat input is element-sharded: a section is
    cut inside ONE device's range of it and put on that device, a shard is
    joined there, and nothing crosses between devices."""
    from parameter_server_distributed_tpu.parallel.mesh import MeshConfig

    def make():
        return Trainer(_Model(), mesh_config=MeshConfig(fsdp=4))

    trainer, reference = make(), make()
    assert trainer._padded_in == 88 > trainer._packed_size
    # 22 elements a device, ten a section: 10 + 10 + 2, four times
    assert trainer._cuts == [(d + a, min(d + a + 10, d + 22))
                             for d in range(0, 88, 22) for a in (0, 10, 20)]
    assert trainer._shards == [[0, 1, 2], [3, 4, 5], [6, 7, 8], [9, 10, 11]]
    holds = {d: held[0].indices(88)[:2] for d, held in
             trainer._flat_sharding.devices_indices_map((88,)).items()}
    puts = []
    put = trainer._put_landed
    trainer._put_landed = lambda flat, s: (
        puts.append((s, put(flat, s))), puts[-1][1])[1]
    worker = _worker(trainer)
    store = _store(70)
    convert = worker._chunk_converter({})
    head, tail = _served(store)
    convert(head)               # emb/w, head/b, layer/w: all but element 84
    loan = trainer._loans[trainer._pack_turn]
    assert sorted(loan._whole) == [0, 1, 2] and sorted(loan._parts[3]) == [9]
    convert(tail)
    # [86, 88) holds padding alone: put with the join of its shard
    assert sorted(loan._whole) == [0, 1, 2, 3]
    assert sorted(s for s, _ in puts) == list(range(12))
    for s, section in puts:
        device, = section.devices()
        a, b = trainer._cuts[s]
        assert holds[device][0] <= a < b <= holds[device][1]
    for k, whole in loan._whole.items():
        device, = whole.devices()
        assert holds[device] == trainer._ranges[k][:2] and whole.shape == (22,)
    landed = _land(worker, _served(store))
    (out, flat), uploaded, streamed = _counted(
        lambda: trainer._dispatch_step(landed, _batch(0)))
    assert uploaded == streamed == 4 * 88
    assert flat is None                           # recording is off
    want, _ = reference._dispatch_step(
        {n: a.copy() for n, a in store.items()}, _batch(0))
    assert np.asarray(out).tobytes() == np.asarray(want).tobytes()
    # one device's shard written behind the loan's back: that one goes up
    # at dispatch, the three others are taken from the device
    landed = _land(worker, _served(store))
    landed["scale"] = store["scale"]              # element 84: the last shard
    _, uploaded, streamed = _counted(
        lambda: trainer._dispatch_step(landed, _batch(1)))
    assert (uploaded, streamed) == (4 * 88, 4 * 66)


def test_a_recorded_step_waits_for_its_upload_once_and_for_every_section(
        monkeypatch):
    trainer = Trainer(_Model())
    worker = _worker(trainer)
    waited = []
    wait = trainer_mod._wait_for_upload
    monkeypatch.setattr(trainer_mod, "_wait_for_upload",
                        lambda uploaded: (waited.append(uploaded),
                                          wait(uploaded)))
    obs_trace.clear()
    obs_trace.enable(True)
    try:
        landed = _land(worker, _served(_store(8)))
        buckets = trainer.compute_gradient_buckets(landed, _batch(0),
                                                   bucket_bytes=64)
        assert np.isfinite(buckets.loss)
        dict(buckets)
        trainer.compute_gradients(landed, _batch(1))
        spans = obs_trace.spans()
    finally:
        obs_trace.enable(False)
        obs_trace.clear()
    assert len(waited) == 2
    # the flat array every section was joined into
    assert all(isinstance(u, jax.Array) and u.shape == (PACKED,)
               for u in waited)
    names = [s["name"] for s in spans]
    assert names.count("worker/device_wait/upload") == 2
    # one span a section that went up as it landed, one for their join,
    # one a dispatch
    h2d = [s for s in spans if s["name"] == "worker/h2d"]
    early = [s for s in h2d if "section" in s["args"]]
    assert sorted(s["args"]["section"] for s in early) == list(range(9))
    assert sum(s["args"]["bytes"] for s in early) == 4 * PACKED
    join, = [s for s in h2d if "joined" in s["args"]]
    assert join["args"]["joined"] == 9 and join["ts"] >= max(
        s["ts"] for s in early)
    early.append(join)
    assert [s["args"]["bytes"] for s in h2d if s not in early] \
        == [0, 4 * PACKED]
    decode = [s for s in spans if s["name"] == "rpc/client/decode"]
    assert all(any(d["ts"] <= s["ts"] and s["ts"] + s["dur"]
                   <= d["ts"] + d["dur"] + 2e-4 for d in decode)
               for s in early)
    # with recording off nothing is kept to wait for
    del waited[:]
    trainer.compute_gradients(_land(worker, _served(_store(9))), _batch(2))
    assert waited == []


def test_the_gradient_leaves_the_device_as_its_buckets_reach_the_host():
    """So that the parameters the pull brings do not stand beside it: a
    bucket's device slice goes once it is on the host, the flat output
    with the last bucket cut from it, and a replay (the unary fallback,
    a ring that failed) reads the host's copies."""
    trainer = Trainer(_Model())
    store = _store(90)
    want, want_loss = Trainer(_Model()).compute_gradients(store, _batch(3))
    buckets = trainer.compute_gradient_buckets(store, _batch(3),
                                               bucket_bytes=64)
    assert buckets.num_buckets > 3
    assert buckets.loss == want_loss              # bucket 0, and only it
    assert buckets._device is not None and buckets._slices[0] is None
    tensors = iter(buckets)
    first = next(tensors)                         # half a replay: stops here
    assert buckets._device is not None
    got = dict([first, *tensors])
    assert buckets._device is None and not any(buckets._slices)
    assert _bits(got) == _bits(want)
    fetched = []
    buckets.on_fetch = lambda i, n: fetched.append(i)
    assert _bits(dict(buckets)) == _bits(want) and buckets.loss == want_loss
    assert fetched == []
