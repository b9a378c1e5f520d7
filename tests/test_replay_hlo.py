"""tools/replay_hlo.py's HLO-dump comparison — the fused-replay fault
mechanism experiment costs chip time per shot, so its
canonicalization and verdict logic must be right before it ever sees
hardware. Pins: float literals survive id-stripping (a constant that
differs between clean/poisoned programs is the evidence the tool exists
to find), filename module-counter normalization, and every verdict arm."""

import importlib.util
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def rh():
    spec = importlib.util.spec_from_file_location(
        "replay_hlo", os.path.join(REPO, "tools", "replay_hlo.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["replay_hlo"] = mod
    spec.loader.exec_module(mod)
    return mod


def test_canon_strips_ids_keeps_floats(rh):
    txt = ("HloModule jit__hashed_replay_epochs.123\n"
           "%fusion.4 = f32[8]{0} fusion(%param.1), kind=kLoop, "
           "metadata={op_name=\"jit(replay)/scan\" source_line=42}\n"
           "ROOT %c.2 = f32[] constant(1.25)\n")
    canon = rh._canon_hlo(txt)
    assert "1.25" in canon, "float literal must survive"
    assert "jit__hashed_replay_epochs.123" not in canon
    assert "%fusion.4" not in canon and "%c.2" not in canon
    assert "metadata=" not in canon
    # identical programs with different unique ids canonicalize equal
    txt2 = (txt.replace("epochs.123", "epochs.77")
            .replace("%fusion.4", "%fusion.9").replace("%c.2", "%c.3"))
    assert rh._canon_hlo(txt2) == canon
    # a DIFFERENT constant stays different (the round-5 review regression)
    assert rh._canon_hlo(txt.replace("1.25", "1.5")) != canon


def _write_dump(d, name, body):
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, name), "w") as f:
        f.write(body)


def test_replay_dumps_normalizes_filenames(rh, tmp_path):
    d = str(tmp_path / "dump")
    _write_dump(d, "module_0012.jit__hashed_replay_epochs.34."
                   "tpu_after_optimizations.txt", "ROOT %x.1 = f32[] add\n")
    _write_dump(d, "module_0012.jit_other.9.tpu_after_optimizations.txt",
                "not a replay module\n")
    out = rh.replay_dumps(d)
    assert list(out) == ["jit__hashed_replay_epochs.tpu_after_optimizations.txt"]
    # same module dumped under a different process counter + unique id
    d2 = str(tmp_path / "dump2")
    _write_dump(d2, "module_0099.jit__hashed_replay_epochs.77."
                    "tpu_after_optimizations.txt", "ROOT %x.8 = f32[] add\n")
    assert rh.replay_dumps(d2) == out


def _fake_cells(poison_fault=True, clean_ok=True):
    return [
        {"cell": "clean", "stages": ["replay"], "ok": clean_ok,
         "stages_completed": ["replay"], "rc": 0, "device_fault": False,
         "wall_s": 1.0},
        {"cell": "poisoned", "stages": ["fitnp", "replay"], "ok": False,
         "stages_completed": ["fitnp"], "rc": 1,
         "device_fault": poison_fault, "wall_s": 1.0},
    ]


def _verdict_of(rh, tmp_path, capsys, monkeypatch, clean_files,
                poison_files, poison_fault=True, root="hlo"):
    import argparse
    import json

    croot = str(tmp_path / root)
    for name, body in clean_files.items():
        _write_dump(croot + "_clean", name, body)
    for name, body in poison_files.items():
        _write_dump(croot + "_poisoned", name, body)
    cells = _fake_cells(poison_fault)
    monkeypatch.setattr(
        rh, "run_cell",
        lambda name, stages, dump_dir, chunk_rows, wall_s:
        cells[0] if name == "clean" else cells[1])
    args = argparse.Namespace(chunk_rows=8, wall_s=1.0, dump_root=croot)
    rh._compare(args)
    out = capsys.readouterr().out
    last = [ln for ln in out.splitlines() if '"replay_fault_hlo"' in ln][-1]
    return json.loads(last)


F = "module_0001.jit__hashed_replay_epochs.1.tpu_after_optimizations.txt"


def test_verdict_runtime_state(rh, tmp_path, capsys, monkeypatch):
    v = _verdict_of(rh, tmp_path, capsys, monkeypatch,
                    {F: "ROOT %a.1 = f32[] constant(1.25)\n"},
                    {F: "ROOT %a.9 = f32[] constant(1.25)\n"})
    assert v["hlo_identical"] is True
    assert v["verdict"].startswith("runtime-state")
    assert v["value"] == 1 and v["poisoned_fault"] is True


def test_verdict_program_content(rh, tmp_path, capsys, monkeypatch):
    v = _verdict_of(rh, tmp_path, capsys, monkeypatch,
                    {F: "ROOT %a.1 = f32[] constant(1.25)\n"},
                    {F: "ROOT %a.1 = f32[] constant(1.5)\n"})
    assert v["hlo_identical"] is False
    assert v["verdict"].startswith("program-content")
    assert v["differing_modules"]


def test_verdict_module_set_mismatch_and_inconclusive(rh, tmp_path, capsys, monkeypatch):
    extra = "module_0002.jit_replay_extra.2.tpu_after_optimizations.txt"
    v = _verdict_of(rh, tmp_path, capsys, monkeypatch,
                    {F: "ROOT %a.1 = f32[] add\n"},
                    {F: "ROOT %a.7 = f32[] add\n",
                     extra: "ROOT %b.1 = f32[] mul\n"})
    assert v["hlo_identical"] is False
    assert v["verdict"].startswith("module-set-mismatch")
    assert v["modules_only_poisoned"]

    v2 = _verdict_of(rh, tmp_path, capsys, monkeypatch, {}, {},
                     root="hlo_empty")
    assert v2["verdict"].startswith("inconclusive")
    assert v2["value"] == 1, "inconclusive must still bank (nonzero value)"


def test_verdict_not_reproduced_still_consistent(rh, tmp_path, capsys,
                                                 monkeypatch):
    """A window where the poison cell happens NOT to fault must still bank
    an interpretable verdict (identical HLO => consistent-with-runtime-state
    wording), not a false 'runtime-state' claim."""
    v = _verdict_of(rh, tmp_path, capsys, monkeypatch,
                    {F: "ROOT %a.1 = f32[] add\n"},
                    {F: "ROOT %a.5 = f32[] add\n"},
                    poison_fault=False, root="hlo_norepro")
    assert v["hlo_identical"] is True and v["poisoned_fault"] is False
    assert v["verdict"].startswith("fault not reproduced")
