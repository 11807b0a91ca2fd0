"""Tooling ports (reference ``tools/``): parse_log markdown tables,
rec2idx index reconstruction, kill-mxnet command construction,
diagnose report."""
import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
TOOLS = os.path.join(REPO, "tools")


def _load(fname):
    from mxnet_tpu.test_utils import load_module_by_path

    return load_module_by_path(os.path.join(TOOLS, fname))


def test_parse_log_markdown():
    pl = _load("parse_log.py")
    lines = [
        "INFO:root:Epoch[0] Train-accuracy=0.5",
        "INFO:root:Epoch[0] Validation-accuracy=0.4",
        "INFO:root:Epoch[0] Time cost=1.5",
        "INFO:root:Epoch[1] Train-accuracy=0.8",
        "noise line",
    ]
    d = pl.parse(lines)
    assert d[0] == [0.5, 0.4, 1.5]
    assert d[1][0] == 0.8
    md = pl.to_markdown(d)
    assert md.splitlines()[0].startswith("| epoch |")


def test_rec2idx_roundtrip(tmp_path):
    from mxnet_tpu import recordio

    rec = str(tmp_path / "a.rec")
    w = recordio.MXRecordIO(rec, "w")
    for i in range(7):
        w.write(b"payload%d" % i)
    w.close()
    r2i = _load("rec2idx.py")
    assert r2i.create_index(rec, rec + ".idx") == 7
    r = recordio.MXIndexedRecordIO(rec + ".idx", rec, "r")
    assert r.read_idx(5) == b"payload5"
    r.close()


def test_kill_mxnet_command():
    km = _load("kill-mxnet.py")
    cmd = km.kill_command("bob", "train.py")
    # shlex-quoted fixed-string grep (round-4 hardening): metachars inert
    assert "grep -F -- train.py" in cmd and "u=bob" in cmd and "kill -9" in cmd
    import shlex
    hostile = "x'; rm -rf /; '"
    assert shlex.quote(hostile) in km.kill_command("bob", hostile)


def test_diagnose_runs():
    res = subprocess.run([sys.executable, os.path.join(TOOLS, "diagnose.py")],
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-500:]
    assert "Framework Info" in res.stdout
    assert "jax" in res.stdout


# -- per-rank trace merging (ISSUE 12) ---------------------------------------
def _rank_trace(tmp_path, rank, name, via):
    """A tiny chrome trace carrying its rank via clock_sync args, event
    args, or only the filename."""
    import json

    events = [{"name": "clock_sync", "ph": "M", "pid": 0,
               "args": {"unix_ts": 1000.0 + rank, "trace_ts_us": 0.0}},
              {"name": name, "ph": "X", "ts": 10.0, "dur": 5.0, "pid": 0,
               "tid": 1, "args": {}}]
    if via == "clock_sync":
        events[0]["args"]["rank"] = rank
        fname = "trace-%s.json" % name
    elif via == "args":
        events[1]["args"]["rank"] = rank
        fname = "trace-%s.json" % name
    else:  # filename only
        fname = "trace-rank%d-%s.json" % (rank, name)
    path = tmp_path / fname
    path.write_text(json.dumps({"traceEvents": events}))
    return str(path)


def test_trace_merge_merges_on_rank_label(tmp_path):
    """Per-rank files land on rank-labeled pid namespaces: two files of
    the SAME rank share one track group, different ranks get their own,
    and every non-meta event gains the queryable args.rank."""
    import json

    tm = _load("trace_merge.py")
    out = str(tmp_path / "merged.json")
    f0a = _rank_trace(tmp_path, 0, "step_a", "clock_sync")
    f0b = _rank_trace(tmp_path, 0, "step_b", "args")
    f1 = _rank_trace(tmp_path, 1, "step_c", "filename")
    assert tm.main([f0a, f0b, f1, "-o", out]) == 0
    merged = json.load(open(out))["traceEvents"]
    slices = {ev["name"]: ev for ev in merged if ev.get("ph") == "X"}
    # same rank -> same pid namespace; different rank -> different
    assert slices["step_a"]["pid"] == slices["step_b"]["pid"]
    assert slices["step_c"]["pid"] != slices["step_a"]["pid"]
    assert slices["step_a"]["args"]["rank"] == 0
    assert slices["step_c"]["args"]["rank"] == 1
    labels = {ev["pid"]: ev["args"]["name"] for ev in merged
              if ev.get("ph") == "M" and ev.get("name") == "process_name"}
    assert labels[slices["step_a"]["pid"]] == "rank 0"
    assert labels[slices["step_c"]["pid"]] == "rank 1"


def test_trace_merge_mixed_rank_file_keeps_own_namespace(tmp_path):
    """A file carrying SEVERAL event ranks (e.g. a previous merge output
    fed back in) has no single file rank — it must keep its own pid
    namespace instead of collapsing every rank into the first one."""
    import json

    tm = _load("trace_merge.py")
    events = [{"name": "a", "ph": "X", "ts": 1.0, "dur": 1.0, "pid": 0,
               "tid": 1, "args": {"rank": 0}},
              {"name": "b", "ph": "X", "ts": 2.0, "dur": 1.0, "pid": 1,
               "tid": 1, "args": {"rank": 1}}]
    mixed = tmp_path / "remerged.json"
    mixed.write_text(json.dumps({"traceEvents": events}))
    assert tm.file_rank(str(mixed), events) is None
    # mixed clock_sync records (two flightrec dumps merged) are equally
    # rank-less — the first clock_sync must not claim the file
    syncs = [{"name": "clock_sync", "ph": "M", "pid": 0,
              "args": {"unix_ts": 1.0, "trace_ts_us": 0.0, "rank": r}}
             for r in (0, 1)]
    assert tm.file_rank("remerged2.json", syncs + events) is None
    f1 = _rank_trace(tmp_path, 1, "step_c", "clock_sync")
    out = str(tmp_path / "m.json")
    assert tm.main([str(mixed), f1, "-o", out]) == 0
    merged = json.load(open(out))["traceEvents"]
    by_name = {ev["name"]: ev for ev in merged if ev.get("ph") == "X"}
    # the mixed file's ranks keep their original (namespaced) pids and
    # were NOT folded into rank 1's track group
    assert by_name["a"]["args"]["rank"] == 0
    assert by_name["b"]["args"]["rank"] == 1
    assert by_name["step_c"]["pid"] not in (by_name["a"]["pid"],
                                            by_name["b"]["pid"])


def test_trace_merge_labels_every_pid_track(tmp_path):
    """Profiler-style dumps use one pid per domain — the rank label must
    land on EVERY pid track the file contributes, without overriding an
    embedded process_name."""
    import json

    tm = _load("trace_merge.py")
    events = [{"name": "process_name", "ph": "M", "pid": 2,
               "args": {"name": "my domain"}},
              {"name": "a", "ph": "X", "ts": 1.0, "dur": 1.0, "pid": 0,
               "tid": 1, "args": {}},
              {"name": "b", "ph": "X", "ts": 2.0, "dur": 1.0, "pid": 2,
               "tid": 1, "args": {}}]
    f = tmp_path / "trace-rank3-prof.json"
    f.write_text(json.dumps({"traceEvents": events}))
    out = str(tmp_path / "m.json")
    assert tm.main([str(f), "-o", out]) == 0
    merged = json.load(open(out))["traceEvents"]
    labels = {}
    for ev in merged:
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            labels.setdefault(ev["pid"], ev["args"]["name"])
    by_name = {ev["name"]: ev for ev in merged if ev.get("ph") == "X"}
    assert labels[by_name["a"]["pid"]] == "rank 3"
    assert labels[by_name["b"]["pid"]] == "my domain"  # not overridden


def test_trace_merge_explicit_rank_flag(tmp_path):
    import json

    tm = _load("trace_merge.py")
    out = str(tmp_path / "merged.json")
    # file with a stale EMBEDDED per-event rank: --rank must override it
    # everywhere — track label and event args agree
    f = _rank_trace(tmp_path, 0, "step_x", "args")
    assert tm.main([f, "-o", out, "--rank", "3"]) == 0
    merged = json.load(open(out))["traceEvents"]
    sl = [ev for ev in merged if ev.get("ph") == "X"][0]
    assert sl["args"]["rank"] == 3
    labels = [ev["args"]["name"] for ev in merged
              if ev.get("ph") == "M" and ev.get("name") == "process_name"]
    assert "rank 3" in labels


def test_trace_summary_accepts_per_rank_files(tmp_path, capsys):
    ts = _load("trace_summary.py")
    f0 = _rank_trace(tmp_path, 0, "op_shared", "clock_sync")
    f1 = _rank_trace(tmp_path, 1, "op_shared", "filename")
    # merged accounting: one row with both ranks' calls
    kind = ["--device-kind", "TPU v5 lite"]
    assert ts.main([f0, f1] + kind) == 0
    out = capsys.readouterr().out
    assert "ranks 0,1 over 2 file(s)" in out
    import re

    row = [l for l in out.splitlines() if l.startswith("op_shared")]
    assert row and re.search(r"\s2\s", row[0]), row  # 2 calls merged
    # --per-rank keeps them apart
    assert ts.main([f0, f1, "--per-rank"] + kind) == 0
    out = capsys.readouterr().out
    assert any(l.startswith("r0/op_shared") for l in out.splitlines())
    assert any(l.startswith("r1/op_shared") for l in out.splitlines())


def test_local_launcher_refuses_to_share_tpu_chips(monkeypatch):
    """A chip belongs to one process: on a host with TPU device nodes the
    local launcher refuses -n > 1 unless JAX_PLATFORMS keeps the children
    off the TPU (the CPU fake cluster)."""
    launch = _load("launch.py")
    monkeypatch.setattr(launch.glob, "glob",
                        lambda pat: ["/dev/vfio/0"] if "vfio" in pat else [])
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert not launch._children_would_share_tpu()
    for platforms in ("", "tpu,cpu"):
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
        assert launch._children_would_share_tpu()
        with pytest.raises(SystemExit, match="a chip belongs to one process"):
            launch.launch_local(2, [sys.executable, "-c", "pass"])
    assert "jax" not in launch.__dict__  # the parent stays off JAX
