"""The plain reference and the durable-image check catch wrong answers
and lost acknowledged writes."""
import types

import numpy as np

from bench import data, durable

INS, NF = data.INSERTED, data.NOT_FOUND


def _op(kind, key, value=0, status=INS, found=False, result=0, sub=0,
        ack=1):
    return types.SimpleNamespace(kind=kind, key=key, value=value,
                                 status=status, found=found, result=result,
                                 h_sub=sub, h_ack=ack)


def _ref():
    return data.Reference(np.array([10, 20, 30], np.uint64),
                          np.array([1, 2, 3], np.uint32))


def test_make_data_is_deterministic_for_large_seeds():
    a = data.make_data(2**33 + 7, 1000, 100)
    b = data.make_data(2**33 + 7, 1000, 100)
    c = data.make_data(2**33 + 8, 1000, 100)
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    assert not np.array_equal(a[0], c[0])
    keys, vals, spare = a
    assert np.unique(np.concatenate([keys, spare])).size == 1100
    assert (vals > 0).all() and (keys > 0).all()


def test_make_data_keeps_the_configured_key_width():
    keys, _, spare = data.make_data(2**31 + 5, 100000, 1000, key_bytes=4)
    both = np.concatenate([keys, spare])
    assert np.unique(both).size == 101000
    assert both.max() < 2**32 and both.min() > 0


def test_correct_answers_pass():
    ops = [_op("read", 10, found=True, result=1),
           _op("read", 99, found=False),
           _op("update", 20, 7, ack=1),
           _op("read", 20, found=True, result=7, sub=1, ack=2)]
    assert data.check_ops(_ref(), ops) == {
        "reads": 3, "writes": 1, "read_wrong": 0, "write_status_wrong": 0}


def test_wrong_value_is_caught():
    ops = [_op("read", 10, found=True, result=2)]
    assert data.check_ops(_ref(), ops)["read_wrong"] == 1


def test_stale_read_after_acknowledgement_is_caught():
    # the update was acknowledged in harvest 1; a read submitted after it
    # must see the new value
    ops = [_op("update", 30, 9, ack=1),
           _op("read", 30, found=True, result=3, sub=1, ack=2)]
    assert data.check_ops(_ref(), ops)["read_wrong"] == 1


def test_concurrent_read_may_see_either_value():
    ops = [_op("update", 30, 9, ack=2),
           _op("read", 30, found=True, result=3, sub=1, ack=2),
           _op("read", 30, found=True, result=9, sub=1, ack=2)]
    assert data.check_ops(_ref(), ops)["read_wrong"] == 0


def test_wrong_status_is_caught():
    ops = [_op("update", 99, 5, status=INS),        # absent: NOT_FOUND
           _op("insert", 10, 5, status=INS)]        # present: EXISTS
    assert data.check_ops(_ref(), ops)["write_status_wrong"] == 2


def test_batch_order_sets_the_final_value():
    ref = _ref()
    ops = [_op("update", 10, 5, ack=1), _op("update", 10, 6, ack=1),
           _op("read", 10, found=True, result=6, sub=1, ack=2)]
    assert data.check_ops(ref, ops)["read_wrong"] == 0
    assert ref.state_at(10, 1) == 6


def test_lost_acknowledged_write_is_caught_in_the_pool(tmp_path):
    from repro import persist
    from repro.core import DashConfig
    cfg = DashConfig(max_segments=4, dir_depth_max=2, init_depth=2)
    keys = np.arange(1, 201, dtype=np.uint64) * 7919
    vals = np.arange(1, 201, dtype=np.uint32)
    path = str(tmp_path / "t.pool")
    table = persist.create(path, cfg)
    table.insert(keys, vals)
    table.flush()
    ref = data.Reference(keys, vals)
    ref.apply_write("update", int(keys[0]), 77, INS, 1)
    table.update(keys[:1], [77])
    table.flush()
    table.writeback.pool.close()
    assert durable.lost_writes(ref, path, cfg.num_buckets) == 0
    # an acknowledged write the pool never received
    ref.apply_write("update", int(keys[1]), 78, INS, 2)
    ref.apply_write("insert", 5, 79, INS, 2)
    assert durable.lost_writes(ref, path, cfg.num_buckets) == 2
