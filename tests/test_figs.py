"""Paper-figure CLI (``repro.launch.figs``): the cheapest figure runs end to
end in quick mode and prints one ``name,derived`` row per sweep point."""
import math
import sys

import pytest

from repro.launch import figs


def _run(monkeypatch, *argv):
    monkeypatch.setattr(figs, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(sys, "argv", ["figs", *argv])
    figs.main()


def test_fig14_quick_rows(monkeypatch, capsys):
    _run(monkeypatch, "--only", "fig14", "--seed", "0")
    lines = capsys.readouterr().out.strip().splitlines()
    assert lines[0] == "name,derived"
    rows = [line.split(",") for line in lines[1:]]
    assert [name for name, _ in rows] == [
        "fig14.cxl_70ns", "fig14.cxl_150ns", "fig14.cxl_250ns",
        "fig14.cxl_400ns"]
    perf = [float(d.removeprefix("norm_perf=")) for _, d in rows]
    assert all(math.isfinite(p) and p > 0 for p in perf)
    # the perf normalized to an uncompressed device rises with CXL latency:
    # the link round trip both pay dilutes the compression overheads
    assert perf == sorted(perf)


def test_unmatched_only_is_an_error(monkeypatch, capsys):
    with pytest.raises(SystemExit) as e:
        _run(monkeypatch, "--only", "fig99")
    assert e.value.code == 2
    assert "matches no figure" in capsys.readouterr().err
