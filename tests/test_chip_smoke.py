"""chip_smoke.py rehearsed on the CPU at a tiny size (on-chip-measurement
guide, section 2.1): every phase of the one-chip flow runs through its
``main(argv)`` with the device check and the compiled-kernel tag stubbed HERE
— the program itself has no option for that — and the captured stdout's last
line must be the contract's JSON object: exactly ``ok`` and ``device``, and in
``device`` exactly ``platform``, ``kind``, ``count``. Without the stub the
script must refuse to run at all."""

import json

import pytest

import chip_smoke

TINY = ["--seed", "3", "--series", "1024", "--capacity", "128", "--scrapes",
        "36", "--more-scrapes", "3", "--gauge-series", "512", "--hist-series",
        "64"]


def test_refuses_to_run_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main(TINY)
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_phases_at_tiny_size_end_in_the_contract_line(monkeypatch, capsys):
    stub = {"platform": "tpu", "kind": "stubbed in the test", "count": 1}
    monkeypatch.setattr(chip_smoke, "find_device", lambda chips: dict(stub))
    # on the CPU the kernels are interpreted, and every route says so
    monkeypatch.setattr(chip_smoke, "EXPECT_TAG", "pallas-interpret")
    assert chip_smoke.main(TINY) == 0
    out = capsys.readouterr().out
    assert out.endswith("\n") and not out.endswith("\n\n")
    lines = out.splitlines()
    last = json.loads(lines[-1])
    assert set(last) == {"ok", "device"}
    assert last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"] == stub
    # the earlier lines name every phase's route
    body = "\n".join(lines[:-1])
    for route in ("local-fused[pallas-interpret]",
                  "local-fused-narrow[delta8,pallas-interpret]",
                  "fused-hist-narrow[pallas-interpret]"):
        assert route in body, route
