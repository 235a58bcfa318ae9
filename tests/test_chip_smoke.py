"""CPU rehearsal of ``chip_smoke.py``: its serving, sharing and fleet
phases run end to end at ``.reduced()`` widths (kernels interpreted),
and the script itself refuses to run without a TPU."""
import os
import shutil
import subprocess
import sys

import pytest

from repro.configs import get_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402


def _share_cfgs():
    return [chip_smoke.cut_depth(get_config(n).reduced(), 2)
            for n in chip_smoke.SHARE_ARCHS]


def test_serving_phase_reduced():
    out = chip_smoke.serving_phase(
        get_config(chip_smoke.SERVE_ARCH).reduced(), n_slots=4,
        max_len=128, page_size=8, n_pages=64, segment=4, n_requests=6,
        shared_len=24, suffix_len=8, new_tokens=8, probe_tokens=2)
    assert out["failures"] == []
    assert out["stats"]["prefix_hits"] > 0
    # on the CPU the prefix-shared tokens are bitwise the private ones
    assert out["tokens"]["vs_private"]["requests_differing"] == 0
    assert out["tokens"]["pass1_vs_pass2"]["requests_differing"] == 0
    for path in ("miss", "hit"):
        assert out["logits"][path]["max_rel_err"] <= chip_smoke.LOGIT_TOL
    # every served token is the reference's best; the control is rejected
    assert all(r <= out["bound"] for r in out["regret"].values())
    zeroed = out["logits"]["zeroed_prefix"]
    assert zeroed["max_rel_err"] > chip_smoke.LOGIT_TOL
    assert zeroed["max_regret"] > out["bound"]
    assert out["kernels"] == {"flash_attention": ["interpret"],
                              "flash_decode_paged": ["interpret"]}


def test_sharing_phase_reduced():
    out = chip_smoke.sharing_phase(*_share_cfgs(), batch=2, seq=32)
    assert out["failures"] == []
    assert out["accum_steps"] >= 2
    assert out["bitwise"]
    assert all(d["update_gap"] == 0 for d in out["details"].values())
    assert (out["details"]["B"]["dropped_microbatch_gap"]
            > chip_smoke.UPDATE_TOL)
    assert out["kernels"] == {"flash_attention": ["interpret"]}


def test_fleet_phase_two_agents(tmp_path):
    cfg_a, cfg_b = _share_cfgs()
    plan, specs, report, groups = chip_smoke.fleet_phase(
        cfg_a, cfg_b, n_agents=2, workdir=str(tmp_path), seq=32,
        use_kernels=False)
    assert groups == 2
    assert chip_smoke.fleet_reference(plan, specs, report,
                                      str(tmp_path)) == []


def test_sharing_plan_has_start_reconfig_finish():
    plan, names = chip_smoke.sharing_plan("m0", "m1", n_servers=4)
    kinds = [op.kind for ph in plan.phases for op in ph.ops]
    assert {"start", "reconfig", "finish"} <= set(kinds)
    assert max(len(ph.groups) for ph in plan.phases) == 4
    assert all(len(g) == 2 for ph in plan.phases for g in ph.groups
               if any(n.startswith("B") for n in g))


def test_main_refuses_cpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert "needs a TPU" in str(exc.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_fleet4_refuses_host_without_chips(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    with pytest.raises(SystemExit, match="needs 4 TPU chips"):
        chip_smoke.main(["--fleet4"])


def test_script_alone_fails(tmp_path):
    """Copied away from the repository, the script exits non-zero and
    prints no result line."""
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
