"""2-process multi-controller test (VERDICT r1 'prove multi-host').

Spawns two controller processes through flexflow_tpu.launcher — each owns 4
virtual CPU devices, jax.distributed.initialize wires them (gloo CPU
collectives) — and trains a dp x tp model over the 8-device global mesh,
including the orbax sharded checkpoint save/restore round-trip (each host
writes/reads only its shards). The TPU-pod analog of the reference's
GASNet/MPI multi-node path with control replication (mapper.cc:267-282,
python/flexflow.py mpirun driver).
"""

import os
import re
import socket
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "multihost_worker.py")
OVERLAP_WORKER = os.path.join(REPO, "tests", "overlap_sync_worker.py")
ELASTIC_WORKER = os.path.join(REPO, "tests", "elastic_worker.py")
SERVE_WORKER = os.path.join(REPO, "tests", "multihost_serve_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _launch(worker, process_id, coordinator, cpu_devices, args, *,
            elastic=False, **env_extra):
    """One controller process through flexflow_tpu.launcher, as one of two."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # workers set their own device counts
    env.pop("FF_FAULT", None)
    env["JAX_PLATFORMS"] = ""
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(env_extra)
    return subprocess.Popen(
        [sys.executable, "-m", "flexflow_tpu.launcher", worker,
         "--num-processes", "2", "--process-id", str(process_id),
         "--coordinator", coordinator, "--cpu-devices", str(cpu_devices),
         *(["--elastic"] if elastic else []), "--", *map(str, args)],
        env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)


def _outputs(procs, timeout=400):
    outs = [p.communicate(timeout=timeout)[0] for p in procs]
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid} failed:\n{out[-4000:]}"
    return outs


def _run_workers(worker, *args, timeout=400, **env_extra):
    """Launch two controller processes (4 virtual CPU devices each) and
    return their stdout, asserting both exited 0."""
    coordinator = f"127.0.0.1:{_free_port()}"
    return _outputs([_launch(worker, pid, coordinator, 4, args, **env_extra)
                     for pid in range(2)], timeout)


def _marker(out, name, fields):
    """The one machine-checkable line a worker prints, as a dict."""
    m = re.search(name + " " + " ".join(rf"{f}=(\S*)" for f in fields), out)
    assert m, f"no {name} marker in output:\n{out[-4000:]}"
    return dict(zip(fields, m.groups()))


@pytest.mark.slow  # 10 s 2-process run
def test_two_process_training_via_launcher(tmp_path):
    outs = _run_workers(WORKER, str(tmp_path / "ckpt"))
    losses = []
    for out in outs:
        m = re.search(r"MULTIHOST pid=\d+ loss=([0-9.]+)", out)
        assert m, out[-2000:]
        losses.append(float(m.group(1)))
        assert "ckpt=ok" in out, out[-2000:]
    # SPMD: both controllers computed the same global loss
    assert losses[0] == pytest.approx(losses[1], rel=1e-6)


@pytest.mark.slow  # 34 s 2-process run
def test_two_process_serving_restore_and_decode(tmp_path):
    """Multi-host SERVING leg (VERDICT r3 #9): train -> sharded checkpoint
    -> restore into a fresh model on the 2-process mesh -> KV-cache greedy
    decode under a TP strategy. Both controllers must produce bit-identical
    tokens — closing the train -> checkpoint -> serve story at the
    multi-controller tier the reference's control replication (§2.5)
    corresponds to."""
    outs = _run_workers(SERVE_WORKER, str(tmp_path / "ckpt_serve"),
                        timeout=500)
    token_rows = []
    for out in outs:
        m = re.search(r"MULTIHOST-SERVE pid=\d+ tokens=([0-9,]+)", out)
        assert m, out[-2000:]
        token_rows.append(m.group(1))
    assert token_rows[0] == token_rows[1], \
        f"controllers decoded different tokens:\n{token_rows[0]}\nvs\n" \
        f"{token_rows[1]}"


@pytest.mark.slow  # three 2-process launches
def test_two_process_overlapped_sync_preempt_resumes_bitwise(tmp_path):
    """Overlapped grad sync (bucketed in-scan reduce-scatter + ZeRO-1
    update) on a 2-controller data=8 mesh: preempted by SIGTERM at step 4
    (the async-checkpoint knob degrades to the collective synchronous save,
    with a warning), relaunched, and the resumed loss tail equals the
    uninterrupted 2-process run's bitwise."""
    fields = ("pid", "status", "resumed", "step", "procs", "zero1", "losses")
    total = 8

    def run(ckpt, **env):
        outs = _run_workers(OVERLAP_WORKER, tmp_path / ckpt, total, **env)
        return [_marker(o, "OVERLAPSYNC", fields) for o in outs], outs

    ref, _ = run("ref")
    for mk in ref:
        assert (mk["status"], mk["procs"]) == ("completed", "2"), mk
        assert mk["zero1"] == "1", "ZeRO-1 update must engage on data=8"
    ref_losses = ref[0]["losses"].split(",")
    assert len(ref_losses) == total, ref_losses
    cut, outs = run("cut", FF_FAULT="sigterm@step:4")
    for mk in cut:
        assert (mk["status"], mk["step"]) == ("preempted", "4"), mk
    assert any("single-controller only" in o for o in outs), \
        "multihost async fallback warning expected"
    resumed, _ = run("cut")
    for mk in resumed:
        assert (mk["status"], mk["resumed"]) == ("completed", "4"), mk
        assert mk["losses"].split(",") == ref_losses[4:], (mk, ref_losses)


@pytest.mark.slow  # a 2-process launch and two single-process relaunches
def test_two_process_run_resumes_on_one_survivor_resharded(tmp_path):
    """The changed-topology drill. A 2-process run on data=8 is preempted at
    step 5 with a collective checkpoint. The surviving worker relaunches
    with its OLD multi-host flags against a dead coordinator: the
    launcher's --elastic probe fails fast and continues single-process,
    shrink(4)@resume presents 4 devices, and the resume reshards data=8 ->
    data=4 with grad accumulation doubled (global batch preserved), loss
    still decreasing. Then the COORDINATOR host survives instead: nobody
    knocks, it continues single-process and adopts the checkpoint's
    accum=2 on the unchanged mesh instead of the config's 1."""
    fields = ("pid", "status", "resumed", "step", "mesh", "accum", "procs",
              "loss_ok")
    ckpt = tmp_path / "ckpt"
    for out in _run_workers(ELASTIC_WORKER, ckpt, 10,
                            FF_FAULT="sigterm@step:5"):
        mk = _marker(out, "ELASTIC", fields)
        assert (mk["status"], mk["step"]) == ("preempted", "5"), mk
        assert (mk["procs"], mk["mesh"]) == ("2", "data=8"), mk

    fast = dict(FF_INIT_ATTEMPTS="1", FF_INIT_TIMEOUT_S="5")
    out, = _outputs([_launch(
        ELASTIC_WORKER, 1, f"127.0.0.1:{_free_port()}", 8, (ckpt, 10),
        elastic=True, FF_FAULT="shrink(4)@resume:1", **fast)])
    assert "continuing SINGLE-process" in out, out[-4000:]
    assert "shrink@resume" in out, out[-4000:]
    mk = _marker(out, "ELASTIC", fields)
    assert (mk["status"], mk["step"], mk["resumed"]) \
        == ("completed", "10", "5"), mk
    assert (mk["procs"], mk["mesh"]) == ("1", "data=4"), mk
    assert mk["accum"] == "2", f"accum must double, global batch kept: {mk}"
    assert mk["loss_ok"] == "1", f"post-resume loss not decreasing: {mk}"

    out, = _outputs([_launch(
        ELASTIC_WORKER, 0, f"127.0.0.1:{_free_port()}", 4, (ckpt, 12),
        elastic=True, **fast)])
    assert "no peer knocked" in out, out[-4000:]
    mk = _marker(out, "ELASTIC", fields)
    assert (mk["status"], mk["step"], mk["resumed"]) \
        == ("completed", "12", "10"), mk
    assert (mk["procs"], mk["mesh"], mk["accum"]) == ("1", "data=4", "2"), mk
