"""Model store, im2rec tooling, and env-var config registry tests.

Reference analogs: model_store download/cache behavior
(python/mxnet/gluon/model_zoo/model_store.py), tools/im2rec.py CLI, and
the documented MXNET_* env-var table (faq/env_var.md).
"""
import os
import subprocess
import sys

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config
from mxnet_tpu.gluon.model_zoo import model_store
from mxnet_tpu.gluon.model_zoo import vision

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_model_store_publish_and_pretrained(tmp_path):
    """Offline pretrained flow: train -> save -> publish -> get_model
    (pretrained=True) resolves from the local cache."""
    net = vision.get_model("squeezenet1.0", classes=10)
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((1, 3, 64, 64)))          # materialize deferred shapes
    params_path = tmp_path / "sq.params"
    net.save_parameters(str(params_path))

    root = tmp_path / "store"
    dst = model_store.publish_model_file(str(params_path), "squeezenet1.0",
                                         root=str(root))
    assert os.path.exists(dst)

    net2 = vision.get_model("squeezenet1.0", classes=10, pretrained=True,
                            root=str(root))
    ref = {k: v.data().asnumpy() for k, v in net.collect_params().items()}
    got = {k: v.data().asnumpy() for k, v in net2.collect_params().items()}
    assert set(ref) == set(got)
    for k in ref:
        assert onp.allclose(ref[k], got[k]), k


def test_model_store_missing_raises_actionable(tmp_path):
    with pytest.raises(IOError, match="resnet18_v1"):
        model_store.get_model_file("resnet18_v1", root=str(tmp_path))
    with pytest.raises(ValueError, match="not available"):
        model_store.get_model_file("not_a_model", root=str(tmp_path))


def _make_images(root, classes=("cat", "dog"), per_class=3):
    import cv2

    rng = onp.random.RandomState(0)
    for c in classes:
        d = os.path.join(root, c)
        os.makedirs(d, exist_ok=True)
        for i in range(per_class):
            img = (rng.rand(12, 14, 3) * 255).astype(onp.uint8)
            cv2.imwrite(os.path.join(d, f"{c}{i}.jpg"), img)


def test_im2rec_list_and_pack(tmp_path):
    imgroot = tmp_path / "imgs"
    _make_images(str(imgroot))
    prefix = str(tmp_path / "data")
    tool = os.path.join(REPO, "tools", "im2rec.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    out = subprocess.run(
        [sys.executable, tool, prefix, str(imgroot), "--list",
         "--recursive"], capture_output=True, text=True, timeout=120,
        env=env)
    assert out.returncode == 0, out.stderr
    lst = prefix + ".lst"
    lines = open(lst).read().strip().splitlines()
    assert len(lines) == 6
    labels = {line.split("\t")[1] for line in lines}
    assert labels == {"0.0", "1.0"} or labels == {"0", "1"}

    out = subprocess.run(
        [sys.executable, tool, prefix, str(imgroot), "--resize", "8"],
        capture_output=True, text=True, timeout=240, env=env)
    assert out.returncode == 0, out.stderr
    assert os.path.exists(prefix + ".rec")
    assert os.path.exists(prefix + ".idx")

    # records load through the framework's RecordIO + unpack_img
    from mxnet_tpu import recordio

    rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "r")
    keys = list(rec.keys)
    assert len(keys) == 6
    header, img = recordio.unpack_img(rec.read_idx(keys[0]))
    assert img.shape[0] >= 8 and img.shape[1] >= 8
    assert header.label in (0.0, 1.0)


def test_naive_engine_toggle(monkeypatch):
    """MXNET_ENGINE_TYPE=NaiveEngine flips ops to synchronous dispatch
    mid-process (the knob is uncached — its debugging role requires it)."""
    import mxnet_tpu as mx
    from mxnet_tpu import engine

    assert not engine.is_naive()
    monkeypatch.setenv("MXNET_ENGINE_TYPE", "NaiveEngine")
    assert engine.is_naive()
    a = mx.nd.array([1.0, 2.0])
    out = mx.nd.broadcast_add(a, a)  # runs the sync path
    assert out.asnumpy().tolist() == [2.0, 4.0]
    monkeypatch.delenv("MXNET_ENGINE_TYPE")
    assert not engine.is_naive()


def test_im2rec_shuffle_false(tmp_path):
    """--shuffle False must actually disable shuffling (argparse type=bool
    would treat the string \"False\" as truthy)."""
    imgroot = tmp_path / "imgs"
    _make_images(str(imgroot))
    tool = os.path.join(REPO, "tools", "im2rec.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    orders = []
    for run in range(2):
        prefix = str(tmp_path / f"data{run}")
        out = subprocess.run(
            [sys.executable, tool, prefix, str(imgroot), "--list",
             "--recursive", "--shuffle", "False"], capture_output=True,
            text=True, timeout=120, env=env)
        assert out.returncode == 0, out.stderr
        lines = open(prefix + ".lst").read().strip().splitlines()
        orders.append([l.split("\t")[-1] for l in lines])
    assert orders[0] == orders[1] == sorted(orders[0])


def test_config_registry():
    v = config.get("MXNET_KVSTORE_BIGARRAY_BOUND")
    assert v == 1000000
    with pytest.raises(KeyError):
        config.get("MXNET_NOT_DECLARED")

    config.declare("MXNET_TEST_KNOB", int, 7, "test knob",
                   validator=lambda x: x > 0, subsystem="testing")
    assert config.get("MXNET_TEST_KNOB") == 7
    os.environ["MXNET_TEST_KNOB"] = "12"
    config.refresh("MXNET_TEST_KNOB")
    assert config.get("MXNET_TEST_KNOB") == 12
    os.environ["MXNET_TEST_KNOB"] = "-3"
    config.refresh("MXNET_TEST_KNOB")
    with pytest.raises(ValueError, match="failed validation"):
        config.get("MXNET_TEST_KNOB")
    del os.environ["MXNET_TEST_KNOB"]
    config.refresh("MXNET_TEST_KNOB")

    # a call-site default applies to that call only — it must never be
    # cached as the variable's value for other callers, and it is validated
    assert config.get("MXNET_TEST_KNOB", default=5000) == 5000
    assert config.get("MXNET_TEST_KNOB") == 7   # declared default intact
    with pytest.raises(ValueError, match="call-site default"):
        config.get("MXNET_TEST_KNOB", default=-1)
    config.VARIABLES.pop("MXNET_TEST_KNOB")   # keep the registry pristine

    md = config.to_markdown()
    assert "MXNET_KVSTORE_BIGARRAY_BOUND" in md
    assert "| Variable | Type | Default | Description |" in md


def test_env_vars_doc_in_sync():
    """docs/ENV_VARS.md is generated from the registry and committed; it
    must not go stale."""
    path = os.path.join(REPO, "docs", "ENV_VARS.md")
    committed = open(path).read()
    assert committed == config.to_markdown(), (
        "regenerate docs/ENV_VARS.md: python -c \"import mxnet_tpu.config "
        "as c; open('docs/ENV_VARS.md','w').write(c.to_markdown())\"")

def _tool_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONPATH", None)
    return env


def test_rec2idx_tool(tmp_path):
    from mxnet_tpu.recordio import MXIndexedRecordIO, MXRecordIO

    rec = str(tmp_path / "t.rec")
    w = MXRecordIO(rec, "w")
    payloads = [f"record-{i}".encode() * (i + 1) for i in range(7)]
    for pl in payloads:
        w.write(pl)
    w.close()

    idx = str(tmp_path / "t.idx")
    r = subprocess.run([sys.executable,
                        os.path.join(REPO, "tools", "rec2idx.py"),
                        rec, idx],
                       capture_output=True, text=True, env=_tool_env())
    assert r.returncode == 0, r.stderr
    assert "wrote 7 entries" in r.stdout
    reader = MXIndexedRecordIO(idx, rec, "r")
    assert reader.read_idx(5) == payloads[5]
    assert reader.read_idx(0) == payloads[0]


def test_parse_log_tool(tmp_path):
    log = tmp_path / "train.log"
    log.write_text(
        "INFO Epoch[0] train-accuracy=0.41 time cost=10.5\n"
        "INFO Epoch[0] Speed: 100.0 samples/sec\n"
        "INFO Epoch[1] train-accuracy=0.83 time cost=9.1\n"
        "INFO Epoch[1] validation-accuracy=0.79\n")
    r = subprocess.run([sys.executable,
                        os.path.join(REPO, "tools", "parse_log.py"),
                        str(log), "--metric-names", "accuracy"],
                       capture_output=True, text=True, env=_tool_env())
    assert r.returncode == 0, r.stderr
    assert "| epoch |" in r.stdout
    assert "0.41" in r.stdout and "0.83" in r.stdout and "0.79" in r.stdout


def test_diagnose_tool():
    r = subprocess.run([sys.executable,
                        os.path.join(REPO, "tools", "diagnose.py"),
                        "--probe-timeout", "20"],
                       capture_output=True, text=True, env=_tool_env(),
                       timeout=300)
    assert r.returncode == 0, r.stderr
    assert "mxnet_tpu" in r.stdout
    assert "Devices" in r.stdout
    assert "diagnose: done" in r.stdout


def test_flakiness_checker_stable_test(tmp_path):
    target = tmp_path / "test_stable.py"
    target.write_text("def test_ok():\n    assert 1 + 1 == 2\n")
    r = subprocess.run([sys.executable,
                        os.path.join(REPO, "tools", "flakiness_checker.py"),
                        str(target), "-n", "2", "--seed", "0"],
                       capture_output=True, text=True, env=_tool_env(),
                       timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "stable across 2" in r.stdout


# 4 checker subprocesses, 8 s (PR 28)
def test_flakiness_checker_detects_seed_failure(tmp_path):
    target = tmp_path / "test_seeded.py"
    target.write_text(
        "import os\n"
        "def test_sometimes():\n"
        "    assert int(os.environ['MXNET_TEST_SEED']) % 2 == 0\n")
    r = subprocess.run([sys.executable,
                        os.path.join(REPO, "tools", "flakiness_checker.py"),
                        str(target), "-n", "4", "--seed", "3"],
                       capture_output=True, text=True, env=_tool_env(),
                       timeout=900)
    out = r.stdout
    assert ("FLAKY" in out and "MXNET_TEST_SEED=" in out) or \
        "stable across" in out   # seed luck: all four even is possible
