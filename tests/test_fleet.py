"""Master/agent fleet runtime (DESIGN.md §17): wire protocol units,
heartbeat suspect/dead state machine + lease-epoch fencing against fake
agents (no subprocess, no jax on the master path), and real 2-agent
subprocess runs of the 4-job replay-validation schedule — bit-exact vs
the single-host executor, including with a SIGKILLed agent mid-plan."""
import dataclasses
import json
import socket
import time

import pytest

from repro.checkpoint import checkpoint_crc
from repro.configs import get_config
from repro.core import (ClusterState, InterferenceModel, Job, PerfParams,
                        Simulator)
from repro.core.schedulers import SJF_BSBF
from repro.launch.cluster import (JobSpec, ScheduleExecutor, plan_from_sim)
from repro.launch import fleet as fleet_mod
from repro.launch.fleet import (ChaosKiller, FleetConfig, FleetError,
                                FleetMaster, KillSpec, local_tpu_chips)
from repro.launch.wire import (MessageReader, WireError, send_msg,
                               spec_from_wire, spec_to_wire)
from repro.util.retry import RetryPolicy

pytestmark = pytest.mark.timeout(900)


def _spec(name="minicpm-2b", batch=2, seq=32, **kw):
    cfg = dataclasses.replace(get_config(name).reduced(), dtype="float32")
    return JobSpec(cfg, batch=batch, seq=seq, **kw)


# ===================================================================== #
# Wire protocol
# ===================================================================== #
class TestWire:
    def test_spec_roundtrip_through_json(self):
        spec = _spec("qwen2-vl-2b", batch=4, seed=7, accum_steps=2)
        wire = json.loads(json.dumps(spec_to_wire(spec)))
        back = spec_from_wire(wire)
        assert back == spec          # tuple fields survive the list form
        assert isinstance(back.cfg.mrope_sections, tuple)

    def test_framing_eof_and_bad_frame(self):
        a, b = socket.socketpair()
        try:
            send_msg(a, {"x": 1})
            send_msg(a, {"y": [1, 2]})
            reader = MessageReader(b)
            assert reader.read() == {"x": 1}
            assert reader.read() == {"y": [1, 2]}
            a.sendall(b"not json\n")
            with pytest.raises(WireError, match="bad frame"):
                reader.read()
            a.close()
            assert reader.read() is None    # EOF, never a hang
        finally:
            b.close()

    def test_send_to_closed_socket_raises_wire_error(self):
        a, b = socket.socketpair()
        b.close()
        a.close()
        with pytest.raises(WireError):
            send_msg(a, {"x": 1})


# ===================================================================== #
# Fake-agent harness: state machine + fencing without subprocesses
# ===================================================================== #
class _FakeProc:
    """Stands in for an agent subprocess: records its environment."""
    envs: list = []

    def __init__(self, args, env=None, **kw):
        _FakeProc.envs.append(env)
        self.returncode = None

    def poll(self):
        return self.returncode


class TestChipPinning:
    def test_one_chip_per_agent_and_no_more_agents_than_chips(
            self, tmp_path, monkeypatch):
        monkeypatch.setattr(fleet_mod.subprocess, "Popen", _FakeProc)
        monkeypatch.setattr(fleet_mod, "local_tpu_chips", lambda: 2)
        _FakeProc.envs = []
        m = FleetMaster(str(tmp_path))
        a0 = m.spawn_agent()
        m.spawn_agent()
        assert [e["TPU_VISIBLE_CHIPS"] for e in _FakeProc.envs] == ["0", "1"]
        for env in _FakeProc.envs:
            assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
            assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert (_FakeProc.envs[0]["TPU_PROCESS_PORT"]
                != _FakeProc.envs[1]["TPU_PROCESS_PORT"])
        with pytest.raises(FleetError, match="chip"):
            m.spawn_agent()
        m.agents[a0].proc.returncode = -9      # a0 died: its chip frees
        m.spawn_agent()
        assert _FakeProc.envs[-1]["TPU_VISIBLE_CHIPS"] == "0"

    def test_cpu_agents_are_not_pinned(self, tmp_path, monkeypatch):
        monkeypatch.setattr(fleet_mod.subprocess, "Popen", _FakeProc)
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        _FakeProc.envs = []
        m = FleetMaster(str(tmp_path))
        assert m.n_chips == local_tpu_chips() == 0
        for _ in range(3):
            m.spawn_agent()
        assert all("TPU_VISIBLE_CHIPS" not in e for e in _FakeProc.envs)


class FakeAgent:
    """A hand-driven agent connection: the tests decide exactly when it
    heartbeats, replies, or goes silent."""

    def __init__(self, port, agent_id):
        self.sock = socket.create_connection(("127.0.0.1", port))
        self.sock.settimeout(5.0)
        self.reader = MessageReader(self.sock)
        self.id = agent_id
        send_msg(self.sock, {"type": "hello", "role": "agent",
                             "id": agent_id, "pid": None})

    def heartbeat(self, watermark=None, epoch=None):
        send_msg(self.sock, {"type": "heartbeat", "agent": self.id,
                             "watermark": watermark or {}, "epoch": epoch})

    def send(self, msg):
        send_msg(self.sock, msg)

    def recv(self):
        return self.reader.read()

    def close(self):
        self.sock.close()


def _wait(predicate, timeout=5.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while not predicate():
        if time.monotonic() > deadline:
            raise AssertionError(f"timed out waiting for {msg}")
        time.sleep(0.01)


def _fast_cfg(**kw):
    kw.setdefault("heartbeat_interval", 0.05)
    kw.setdefault("suspect_after", 0.15)
    kw.setdefault("dead_after", 0.4)
    kw.setdefault("retry_policy",
                  RetryPolicy(attempts=3, base=0.01, deadline=5.0))
    return FleetConfig(**kw)


class TestStateMachine:
    def test_missed_heartbeats_suspect_then_dead(self, tmp_path):
        with FleetMaster(str(tmp_path), config=_fast_cfg()) as m:
            m.start(0)
            fa = FakeAgent(m.port, "f0")
            _wait(lambda: m.agents.get("f0", None) is not None
                  and m.agents["f0"].state == "alive", msg="agent up")
            # silence (no close: the socket stays open, like a hung host)
            _wait(lambda: m.agents["f0"].state == "dead", timeout=5.0,
                  msg="dead declaration")
            kinds = [e["kind"] for e in m.events]
            assert "agent_suspect" in kinds and "agent_dead" in kinds
            dead = next(e for e in m.events if e["kind"] == "agent_dead")
            assert dead["reason"] == "heartbeat"
            assert 0.0 <= dead["detection_latency"] < 5.0
            fa.close()

    def test_heartbeat_recovers_suspect_agent(self, tmp_path):
        cfg = _fast_cfg(suspect_after=0.1, dead_after=10.0)
        with FleetMaster(str(tmp_path), config=cfg) as m:
            m.start(0)
            fa = FakeAgent(m.port, "f0")
            _wait(lambda: "f0" in m.agents
                  and m.agents["f0"].state == "alive", msg="agent up")
            _wait(lambda: m.agents["f0"].state == "suspect",
                  msg="suspect")
            fa.heartbeat()
            _wait(lambda: m.agents["f0"].state == "alive",
                  msg="recovery")
            assert any(e["kind"] == "agent_recovered" for e in m.events)
            fa.close()

    def test_watermark_regression_is_counted(self, tmp_path):
        with FleetMaster(str(tmp_path),
                         config=_fast_cfg(dead_after=10.0)) as m:
            m.start(0)
            fa = FakeAgent(m.port, "f0")
            _wait(lambda: "f0" in m.agents
                  and m.agents["f0"].state == "alive", msg="agent up")
            fa.heartbeat({"j": 3})
            _wait(lambda: m.agents["f0"].watermark.get("j") == 3,
                  msg="watermark")
            fa.heartbeat({"j": 1})      # progress must be monotone
            _wait(lambda: m.stats["watermark_regressions"] == 1,
                  msg="regression count")
            fa.close()


class TestFencing:
    def test_zombie_lease_is_fenced_and_job_requeued(self, tmp_path):
        """The acceptance scenario for fencing: an agent takes a lease,
        goes silent past the timeout (unconfirmed death -> its epoch is
        fenced), then wakes up and reports completion — the stale result
        is discarded, and the job re-runs on a second agent whose lease
        excludes the fenced epoch from restore_epochs."""
        with FleetMaster(str(tmp_path), config=_fast_cfg()) as m:
            m.start(0)
            fa = FakeAgent(m.port, "f0")
            _wait(lambda: "f0" in m.agents
                  and m.agents["f0"].state == "alive", msg="agent up")
            m.submit_job({"stub": True}, steps=5, name="j")
            lease = fa.recv()
            assert lease["type"] == "lease"
            assert lease["members"][0]["name"] == "j"
            epoch = lease["epoch"]
            fa.heartbeat({"j": 2}, epoch=epoch)
            # now go silent until declared dead
            _wait(lambda: m.agents["f0"].state == "dead", timeout=5.0,
                  msg="dead declaration")
            assert epoch in m._fenced_epochs
            # zombie resumes and reports a full run: must be discarded
            fenced_before = m.stats["fenced"]
            fa.send({"type": "lease_done", "lease_id": lease["lease_id"],
                     "epoch": epoch, "walltime": 1.0,
                     "report": {"j": {"steps": 5, "resumed_from": 0}}})
            _wait(lambda: m.stats["fenced"] > fenced_before,
                  msg="fenced result")
            assert not m.jobs["j"].finished
            # a fresh agent picks up the requeued job
            fb = FakeAgent(m.port, "f1")
            lease2 = fb.recv()
            assert lease2["type"] == "lease"
            assert lease2["epoch"] != epoch
            assert epoch not in lease2["members"][0]["restore_epochs"]
            fb.heartbeat({"j": 5}, epoch=lease2["epoch"])
            fb.send({"type": "lease_done",
                     "lease_id": lease2["lease_id"],
                     "epoch": lease2["epoch"], "walltime": 2.0,
                     "report": {"j": {"steps": 5, "resumed_from": 0,
                                      "loss": 1.5}}})
            rep = m.wait_for_job("j", timeout=5.0)
            assert rep["finished"] and rep["steps"] == 5
            assert m.jobs["j"].redispatches == 1
            fa.close()
            fb.close()

    def test_cancel_requeued_before_dispatch(self, tmp_path):
        with FleetMaster(str(tmp_path), config=_fast_cfg()) as m:
            m.start(0)
            m.submit_job({"stub": True}, steps=5, name="j")
            assert m.cancel_job("j")
            assert not m.cancel_job("j")        # idempotent
            status = m.status()
            assert status["jobs"]["j"]["cancelled"]
            assert status["queue"] == []

    def test_dispatch_with_no_agents_exhausts_retry_budget(self, tmp_path):
        cfg = _fast_cfg(retry_policy=RetryPolicy(
            attempts=10, base=0.01, cap=0.02, deadline=0.2))
        from repro.util.retry import RetryBudgetExceeded
        with FleetMaster(str(tmp_path), config=cfg) as m:
            m.start(0)
            m.jobs["j"] = __import__(
                "repro.launch.fleet", fromlist=["MasterJob"]).MasterJob(
                name="j", wire_spec={}, total_steps=1, started=True)
            with pytest.raises((RetryBudgetExceeded, FleetError)):
                m._dispatch(("j",), {"j": 1}, ("j",))


# ===================================================================== #
# CLI client path against an in-process master + fake agent
# ===================================================================== #
class TestFleetCLI:
    def test_submit_status_cancel_roundtrip(self, tmp_path, capsys):
        from repro.launch import fleet_cli
        with FleetMaster(str(tmp_path),
                         config=_fast_cfg(dead_after=10.0)) as m:
            m.start(0)
            fa = FakeAgent(m.port, "f0")
            _wait(lambda: "f0" in m.agents
                  and m.agents["f0"].state == "alive", msg="agent up")
            port = str(m.port)
            rc = fleet_cli.main([
                "submit", "--port", port, "--arch", "minicpm-2b",
                "--reduced", "--steps", "2", "--name", "cli-job"])
            assert rc == 0
            assert "submitted cli-job" in capsys.readouterr().out
            lease = fa.recv()
            assert lease["members"][0]["name"] == "cli-job"
            # the wire spec the CLI built reconstructs into a JobSpec
            spec = spec_from_wire(lease["members"][0]["spec"])
            assert spec.cfg.name == "minicpm-2b-reduced"
            fa.send({"type": "lease_done", "lease_id": lease["lease_id"],
                     "epoch": lease["epoch"], "walltime": 0.5,
                     "report": {"cli-job": {"steps": 2,
                                            "resumed_from": 0}}})
            m.wait_for_job("cli-job", timeout=5.0)
            assert fleet_cli.main(["status", "--port", port]) == 0
            out = capsys.readouterr().out
            assert "cli-job: 2/2 finished" in out
            assert fleet_cli.main(
                ["cancel", "--port", port, "cli-job"]) == 1
            assert fleet_cli.main(["queue", "--port", port]) == 0
            fa.close()

    def test_unreachable_master_exits_2(self, capsys):
        from repro.launch import fleet_cli
        sock = socket.create_server(("127.0.0.1", 0))
        port = sock.getsockname()[1]
        sock.close()                      # nothing listens here now
        assert fleet_cli.main(["status", "--port", str(port)]) == 2


# ===================================================================== #
# Real 2-agent subprocess fleet: the 4-job replay-validation schedule
# ===================================================================== #
GB = 2 ** 30


def _perf(alpha=0.01, beta=0.01):
    return PerfParams(alpha_comp=alpha, beta_comp=beta, alpha_comm=0.0,
                      beta_comm=0.0, msg_bytes=0.0, delta=2.0,
                      mem_base=4.0 * GB, mem_per_sample=0.25 * GB,
                      param_bytes=1e8, n_workers=1)


def _replay_plan():
    """The replay-validation schedule (test_schedule_executor._scenario)
    at iters_a=6: donor A spans both GPUs, B/C form the 3-way sharing
    group with donor reconfigs, D queues — 8 phases, 16 total steps."""
    pa, pb = _perf(), _perf(beta=0.008)
    t_a = pa.t_iter(4)
    jobs = [Job(jid=0, model="m0", arrival=0.0, gpus=2, iters=6.0,
                batch=4, perf=pa),
            Job(jid=1, model="m1", arrival=2 * t_a, gpus=1, iters=3.0,
                batch=4, perf=pb),
            Job(jid=2, model="m1", arrival=4 * t_a, gpus=1, iters=4.0,
                batch=4, perf=pb),
            Job(jid=3, model="m0", arrival=6 * t_a, gpus=1, iters=3.0,
                batch=4, perf=pa)]
    cap = pa.mem_bytes(2) + pb.mem_bytes(2) + 0.25 * 0.25 * GB
    interf = InterferenceModel()
    for a in ("m0", "m1"):
        for b in ("m0", "m1"):
            interf.set_pair(a, b, 1.3, 1.3)
    cluster = ClusterState(n_servers=1, gpus_per_server=2,
                           gpu_capacity_bytes=cap)
    sim = Simulator(cluster, jobs, SJF_BSBF(donor_reconfig=True),
                    interference=interf, reconfig_on_release=True)
    sim.run()
    plan = plan_from_sim(sim.log, sim.jobs, sim.interference, cap,
                         names={0: "A", 1: "B", 2: "C", 3: "D"})
    assert max(len(g) for p in plan.phases for g in p.groups
               if p.groups) == 3
    specs = {"A": _spec(batch=4), "B": _spec(batch=4, seed=1),
             "C": _spec(batch=4, seed=2), "D": _spec(batch=4, seed=3)}
    return plan, specs


@pytest.fixture(scope="module")
def replay_reference(tmp_path_factory):
    """Single-host ScheduleExecutor run of the replay plan: the ground
    truth the fleet must match bit-for-bit (per-job final checkpoint
    CRCs, steps, losses)."""
    plan, specs = _replay_plan()
    ref_dir = tmp_path_factory.mktemp("ref")
    totals = {}
    for phase in plan.phases:
        for name, q in phase.quotas:
            totals[name] = totals.get(name, 0) + q
    with ScheduleExecutor(donate=True,
                          checkpoint_dir=str(ref_dir)) as ex:
        for name, spec in specs.items():
            ex.submit(name, spec, totals[name])
        report = ex.execute(plan)
        paths = {name: ex.checkpoint(name) for name in specs}
    crcs = {name: checkpoint_crc(paths[name]) for name in specs}
    assert all(c is not None for c in crcs.values())
    return {"plan": plan, "specs": specs, "report": report,
            "crcs": crcs}


class TestTwoAgentFleet:
    def test_fleet_matches_single_host_bit_exactly(self, tmp_path,
                                                   replay_reference):
        """Satellite 4, failure-free half: a 2-agent fleet run of the
        replay-validation schedule produces the same per-job step counts,
        final losses, and checkpoint content CRCs as the single-host
        executor."""
        ref = replay_reference
        with FleetMaster(str(tmp_path),
                         config=FleetConfig(checkpoint_every=1)) as m:
            m.start(n_agents=2)
            report = m.run_plan(ref["plan"], ref["specs"])
        for name in ref["specs"]:
            assert report[name]["finished"], name
            assert report[name]["steps"] == ref["report"][name]["steps"]
            assert report[name]["crc"] == ref["crcs"][name], \
                f"job {name}: fleet checkpoint diverged from single-host"
            assert report[name]["loss"] == pytest.approx(
                ref["report"][name]["loss"], abs=0)
        assert m.stats["redispatches"] == 0
        assert m.stats["fenced"] == 0

    def test_fleet_survives_sigkill_bit_exactly(self, tmp_path,
                                                replay_reference):
        """Satellite 4, failure half (the PR's acceptance scenario): one
        agent is SIGKILLed mid-step; the master detects it within the
        configured timeout, re-dispatches its group from the last
        checkpoint, and the final params still match the failure-free
        single-host run bit-for-bit."""
        ref = replay_reference
        cfg = FleetConfig(checkpoint_every=1, step_sleep=0.3,
                          heartbeat_interval=0.1, suspect_after=0.5,
                          dead_after=1.0)
        chaos = ChaosKiller([KillSpec(agent="a0", after_steps=2)])
        with FleetMaster(str(tmp_path), config=cfg, chaos=chaos) as m:
            m.start(n_agents=2)
            report = m.run_plan(ref["plan"], ref["specs"])
            assert len(chaos.kills) == 1, "the scripted kill must fire"
            dead = [e for e in m.events if e["kind"] == "agent_dead"]
            assert dead and dead[0]["agent"] == "a0"
            assert dead[0]["killed"]
            # detection within the configured timeout (+ scheduling slack)
            assert dead[0]["detection_latency"] < cfg.dead_after + 1.0
            assert m.stats["redispatches"] >= 1
        for name in ref["specs"]:
            assert report[name]["finished"], name
            assert report[name]["steps"] == ref["report"][name]["steps"]
            assert report[name]["crc"] == ref["crcs"][name], \
                f"job {name}: recovery broke bit-exactness"
            assert report[name]["loss"] == pytest.approx(
                ref["report"][name]["loss"], abs=0)
