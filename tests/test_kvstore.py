"""KVStore façade tests.

Parity: tests/python/unittest/test_kvstore.py (single-process) and
tests/nightly/dist_sync_kvstore.py (multi-process on one box via the
local launcher — each worker pushes known constants and the aggregate
must equal the exact sum; SURVEY.md §4 'Distributed')."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_local_push_pull():
    kv = mx.kv.create("local")
    kv.init(3, mx.nd.ones((2, 3)) * 2)
    out = mx.nd.zeros((2, 3))
    kv.pull(3, out=out)
    np.testing.assert_array_equal(out.asnumpy(), 2)
    # push replaces with the merged sum (reference semantics)
    kv.push(3, [mx.nd.ones((2, 3)) * 4] * 3)
    kv.pull(3, out=out)
    np.testing.assert_array_equal(out.asnumpy(), 12)


def test_local_updater():
    kv = mx.kv.create("local")
    kv.init("w", mx.nd.ones((4,)))

    def upd(key, inp, stored):
        stored._rebind(stored._data + 2 * inp._data)

    kv.set_updater(upd)
    kv.push("w", mx.nd.ones((4,)))
    out = mx.nd.zeros((4,))
    kv.pull("w", out=out)
    np.testing.assert_array_equal(out.asnumpy(), 3)


def test_multi_key():
    kv = mx.kv.create("local")
    kv.init(["a", "b"], [mx.nd.ones((2,)), mx.nd.ones((3,)) * 5])
    oa, ob = mx.nd.zeros((2,)), mx.nd.zeros((3,))
    kv.pull(["a", "b"], out=[oa, ob])
    np.testing.assert_array_equal(oa.asnumpy(), 1)
    np.testing.assert_array_equal(ob.asnumpy(), 5)


def test_pushpull_pure_allreduce():
    kv = mx.kv.create("local")
    kv.init(0, mx.nd.zeros((2,)))
    g = mx.nd.ones((2,)) * 3
    out = mx.nd.zeros((2,))
    kv.pushpull(0, g, out=out)
    np.testing.assert_array_equal(out.asnumpy(), 3)


def test_dist_async_descope():
    with pytest.raises(MXNetError, match="dist_async"):
        mx.kv.create("dist_async")


def test_row_sparse_pull_descope():
    kv = mx.kv.create("local")
    with pytest.raises(MXNetError, match="sparse"):
        kv.row_sparse_pull("x")


_WORKER = textwrap.dedent("""
    import os, sys
    import numpy as np
    sys.path.insert(0, {repo!r})
    import mxnet_tpu as mx

    kv = mx.kv.create("dist_sync")
    rank, size = kv.rank, kv.num_workers
    assert size == 2, size
    kv.init("w", mx.nd.ones((4,)) * 10)
    out = mx.nd.zeros((4,))
    kv.pull("w", out=out)
    np.testing.assert_array_equal(out.asnumpy(), 10)   # broadcast from root
    # each worker pushes (rank+1): aggregate must be exactly 1+2 = 3
    kv.push("w", mx.nd.ones((4,)) * (rank + 1))
    kv.pull("w", out=out)
    np.testing.assert_array_equal(out.asnumpy(), 3)
    # pure allreduce path
    res = mx.nd.zeros((4,))
    kv.pushpull("w", mx.nd.ones((4,)) * (rank + 1), out=res)
    np.testing.assert_array_equal(res.asnumpy(), 3)
    print("WORKER_OK", rank)
""")


@pytest.mark.slow
def test_dist_sync_two_process(tmp_path):
    """mx.kv.create('dist_sync') in a 2-process CPU rig via the launcher."""
    worker = tmp_path / "worker.py"
    worker.write_text(_WORKER.format(repo=REPO))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # children must not inherit the 8-device forcing (1 device per proc ok)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", sys.executable, str(worker)],
        capture_output=True, text=True, env=env, timeout=280)
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert r.stdout.count("WORKER_OK") == 2, (r.stdout, r.stderr)


def test_launcher_refuses_to_share_one_chip_between_local_ranks():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--platform", "tpu", "true"],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 2 and "its own tpu chip" in r.stderr, r.stderr


def test_pushpull_updates_store_and_defaults_out_to_value():
    kv = mx.kv.create("local")
    kv.init(0, mx.nd.zeros((2,)))
    g = mx.nd.ones((2,)) * 3
    kv.pushpull(0, g)                      # out omitted → value in-place
    np.testing.assert_array_equal(g.asnumpy(), 3)
    out = mx.nd.zeros((2,))
    kv.pull(0, out=out)                    # store must see the new value
    np.testing.assert_array_equal(out.asnumpy(), 3)
    with pytest.raises(MXNetError, match="out"):
        kv.pull(0)


def test_set_optimizer_states_roundtrip(tmp_path):
    from mxnet_tpu import optimizer as opt
    kv = mx.kv.create("local")
    kv.set_optimizer(opt.Adam(learning_rate=0.1))
    kv.init("w", mx.nd.ones((3,)))
    kv.push("w", mx.nd.ones((3,)))         # builds Adam state for "w"
    f = str(tmp_path / "states.bin")
    kv.save_optimizer_states(f)
    kv2 = mx.kv.create("local")
    kv2.set_optimizer(opt.Adam(learning_rate=0.1))
    kv2.load_optimizer_states(f)
    assert "w" in kv2._updater_obj.states  # momentum survived the trip
