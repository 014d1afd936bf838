"""`tools/profile_report.py`: the reduction of a profiler trace, held to small
traces written here with the profiler's own protobuf (its text form, which
`ProfileData` turns into an `.xplane.pb`), and `main` rehearsed on the CPU.

The made-up trace is a lagging reader's: three fetches that each decrypt one
window. The device plane is what a v5e's looks like (`/device:TPU:0`; line
`XLA Ops` with an event per operation, named by its whole HLO line, the op name
with its `gcm.*` scope a statistic of the event's METADATA, as a string or as a
reference to one; line `XLA Modules` with an event per program), the host plane
holds the program's spans as TraceAnnotations on two threads.
"""

from __future__ import annotations

import pathlib
import sys

import pytest

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(REPO_ROOT / "tools") not in sys.path:
    sys.path.insert(0, str(REPO_ROOT / "tools"))

import profile_report  # noqa: E402

MS = 1_000_000  # nanoseconds

# (start ms, duration ms, op, the op name its metadata holds)
DEVICE = [
    (1.5, 1.0, "%fusion.1", "jit(_packed_fixed_impl)/jit(_gcm_process_batch)/gcm.ctr/shift_left:"),
    (2.5, 0.9, "%ghash_tree_pallas.1", "jit(_packed_fixed_impl)/jit(_gcm_process_batch)/gcm.ghash/tree:"),
    (3.4, 0.1, "%copy.15", "jit(_packed_fixed_impl)/gcm.pack/concatenate:"),
    (11.5, 1.0, "%fusion.1", "jit(_packed_fixed_impl)/jit(_gcm_process_batch)/gcm.ctr/shift_left:"),
    (12.5, 0.5, "%ghash_tree_pallas.1", "jit(_packed_fixed_impl)/jit(_gcm_process_batch)/gcm.ghash/tree:"),
    # no window launched this one; its source line names ops/gcm.py, which is no scope
    (20.0, 0.5, "%gather.3", "jit(_take)/gather: /root/repo/tieredstorage_tpu/ops/gcm.py:97"),
    (30.2, 0.6, "%fusion.1", "jit(_packed_fixed_impl)/jit(_gcm_process_batch)/gcm.ctr/shift_left:"),
    (30.8, 0.2, "%while.1", None),  # the compiler's own copy loop: no op name at all
]
# (start ms, duration ms, program): the `XLA Modules` line
MODULES = [
    (1.5, 2.0, "jit__packed_fixed_impl(1)"), (11.5, 1.5, "jit__packed_fixed_impl(1)"),
    (20.0, 0.5, "jit__take(2)"), (30.2, 0.8, "jit__packed_fixed_impl(1)"),
]
# (thread, start ms, duration ms, span)
HOST = [
    ("sidecar-http_0", 0.0, 10.0, "gateway.fetch"),
    ("sidecar-http_0", 0.9, 3.2, "transform.decrypt"),
    ("sidecar-http_0", 1.0, 0.2, "transform.launch"),
    ("sidecar-http_0", 1.2, 2.8, "transform.d2h_wait"),
    ("sidecar-http_0", 4.2, 5.7, "gateway.reply_stream"),
    ("sidecar-http_0", 4.3, 0.0, "hot.hit"),
    ("sidecar-http_1", 10.5, 3.2, "gateway.fetch"),
    ("sidecar-http_1", 11.0, 0.3, "transform.launch"),
    ("sidecar-http_1", 11.3, 2.1, "transform.d2h_wait"),
    ("sidecar-http_1", 30.0, 0.1, "transform.launch"),
    ("sidecar-http_1", 30.1, 1.4, "transform.d2h_wait"),
    ("sidecar-http_1", 5.0, 1.0, "PjitFunction(_packed_fixed_impl)"),  # the runtime's own
]


def write_xplane(path: pathlib.Path, host_shift_ms: float = 0.0, host=None,
                 modules=MODULES) -> pathlib.Path:
    """The trace above as an `.xplane.pb`, the host's events `host_shift_ms`
    late against the device's: a host clock that runs ahead."""
    from jax.profiler import ProfileData

    base = 100 * MS  # events start from a line's timestamp; leave room to shift back

    def plane(name: str, lines: dict, op_names: dict | None = None) -> str:
        names = sorted({event[2] for events in lines.values() for event in events})
        ids = {n: i + 1 for i, n in enumerate(names)}
        text = [f'planes {{ name: "{name}"']
        for line, events in lines.items():
            text.append(f'lines {{ name: "{line}" timestamp_ns: 0')
            for start_ms, duration_ms, event in events:
                text.append(
                    f"events {{ metadata_id: {ids[event]} "
                    f"offset_ps: {round((base + start_ms * MS) * 1000)} "
                    f"duration_ps: {round(duration_ms * MS * 1000)} }}"
                )
            text.append("}")
        text.append('stat_metadata { key: 1 value { id: 1 name: "tf_op" } }')
        for n, i in ids.items():
            op_name = (op_names or {}).get(n)
            stats = ""
            if op_name is not None and "ghash" in op_name:
                # the profiler also keeps a string as a reference to a stat
                # metadata of that name
                text.append(
                    f'stat_metadata {{ key: {100 + i} value {{ id: {100 + i} name: "{op_name}" }} }}'
                )
                stats = f"stats {{ metadata_id: 1 ref_value: {100 + i} }}"
            elif op_name is not None:
                stats = f'stats {{ metadata_id: 1 str_value: "{op_name}" }}'
            text.append(
                f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" {stats} }} }}'
            )
        text.append("}")
        return "\n".join(text)

    def hlo(op: str) -> str:  # the trace names an operation by its whole HLO line
        return f"{op} = u8[16,4194320] fusion()"

    threads: dict = {}
    for thread, start_ms, duration_ms, span in HOST if host is None else host:
        threads.setdefault(thread, []).append((start_ms + host_shift_ms, duration_ms, span))
    text = "\n".join([
        plane(
            "/device:TPU:0",
            {"XLA Ops": [(s, d, hlo(op)) for s, d, op, _ in DEVICE], "XLA Modules": modules},
            op_names={hlo(op): op_name for _, _, op, op_name in DEVICE},
        ),
        plane("/host:CPU", threads),
    ])
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return path


@pytest.fixture
def sound(tmp_path):
    return profile_report.reduce(write_xplane(tmp_path / "sound.xplane.pb"))


def test_the_device_plane_is_read_as_it_is(sound):
    assert sound["device_ops"] == 8 and sound["program_spans"] == 11
    assert sound["busy_s"] == pytest.approx(4.8e-3)
    assert sound["window_s"] == pytest.approx(31.5e-3)  # first span's start to last wait's end


def test_clock_check_passes_on_one_clock(sound):
    check = sound["clock_check"]
    assert (check["launches"], check["waits"], check["device_programs"]) == (3, 3, 3)
    assert check["programs_from"] == "XLA Modules"
    assert check["other_device_programs"] == 1  # the gather that no window launched
    assert check["violations"] == 0 and check["worst_skew_us"] == 0.0
    # the offsets the check could not have seen: a device program began
    # 200 us after its launch did, and one ended 400 us before its wait did
    assert check["launch_slack_us_min"] == pytest.approx(200.0)
    assert check["wait_slack_us_min"] == pytest.approx(400.0)


def test_idle_gaps_go_piece_by_piece_to_the_innermost_span(sound):
    idle = sound["idle_gaps"]
    # 0-1.5 ms, 3.5-11.5, 13-20, 20.5-30.2; the last 0.5 ms is under 1 ms
    assert idle["count"] == 4
    assert idle["idle_s"] == pytest.approx(26.2e-3)
    assert idle["by_span_s"] == pytest.approx({
        "gateway.reply_stream": 5.7e-3,  # inside gateway.fetch, which covers it too
        "gateway.fetch": 1.9e-3,         # what its children leave: 0.9 + 0.1 + 0.1 + 0.5 + 0.3
        "transform.d2h_wait": 1.5e-3,    # the device's lead-in and the copy back
        "transform.launch": 0.6e-3,
        "transform.decrypt": 0.2e-3,
    })
    assert list(idle["by_span_s"])[:2] == ["gateway.reply_stream", "gateway.fetch"]
    # 10-10.5 between the two requests, 13.7-20 and 20.5-30 after the second
    assert idle["uncovered_share"] == pytest.approx(16.3 / 26.2)


def test_ready_lateness_pairs_the_watchs_stamps_with_the_programs_ends(tmp_path, sound):
    """The window programs end at 3.5, 13.0 and 31.0 ms; the device watch's
    `device.ready` annotations lie 200, 250 and 400 us after them, on a
    thread of their own, and hold no idle time."""
    assert sound["ready_lateness_us"] == {"count": 0, "unpaired": 3}
    readies = [("device-watch", at, 0.001, "device.ready") for at in (3.7, 13.25, 31.4)]
    report = profile_report.reduce(
        write_xplane(tmp_path / "ready.xplane.pb", host=HOST + readies)
    )
    assert report["ready_lateness_us"] == pytest.approx(
        {"count": 3, "unpaired": 0, "min": 200.0, "p50": 250.0, "p95": 400.0, "max": 400.0}
    )
    assert report["program_spans"] == sound["program_spans"]
    assert report["idle_gaps"]["by_span_s"] == pytest.approx(sound["idle_gaps"]["by_span_s"])
    # a stamp more than programs: counted from the end, the odd one reported
    report = profile_report.reduce(write_xplane(
        tmp_path / "odd.xplane.pb", host=HOST + readies + [("device-watch", 0.2, 0.001, "device.ready")]
    ))
    assert (report["ready_lateness_us"]["count"], report["ready_lateness_us"]["unpaired"]) == (3, 1)
    assert report["ready_lateness_us"]["max"] == pytest.approx(400.0)


def test_a_gap_is_its_launchers_where_a_window_program_ended_it():
    """Launches and window programs pair from the end; a gap that a program
    no window launched ends (or that nothing ends) has no launcher."""
    ran = [[100, 200, "jit__packed_fixed_impl(1)"], [400, 450, "jit__take(2)"],
           [700, 800, "jit__packed_fixed_impl(1)"]]
    programs = [[100, 200], [700, 800]]
    launches = [(5, "warm-up"), (90, "http_0"), (690, "http_1")]  # one launch too many: the first
    gaps = [(0, 100), (200, 400), (450, 700), (800, 900)]
    assert profile_report.launcher_of_gap(gaps, ran, programs, launches) == [
        (0, 100, "http_0"), (200, 400, None), (450, 700, "http_1"), (800, 900, None),
    ]


def test_device_seconds_by_scope(sound):
    assert sound["device_s_by_scope"] == pytest.approx({
        "gcm.ctr": 2.6e-3, "gcm.ghash": 1.4e-3, "unscoped": 0.7e-3, "gcm.pack": 0.1e-3,
    })
    assert list(sound["device_s_by_scope"])[0] == "gcm.ctr"


@pytest.mark.parametrize("shift_ms,violations,worst_us", [
    (3.0, 3, 2800.0),    # host ahead: every program "began" before its launch
    (-3.0, 3, 2600.0),   # host behind: every program "ended" after its wait
    (0.3, 1, 100.0),     # 300 us is past the 200 us of slack, by 100
])
def test_a_skewed_host_clock_is_reported_and_no_gap_is_labelled(
    tmp_path, shift_ms, violations, worst_us
):
    report = profile_report.reduce(
        write_xplane(tmp_path / "skewed.xplane.pb", host_shift_ms=shift_ms)
    )
    check = report["clock_check"]
    assert check["violations"] == violations
    assert check["worst_skew_us"] == pytest.approx(worst_us)
    assert "by_span_s" not in report["idle_gaps"]
    assert "uncovered_share" not in report["idle_gaps"]
    assert report["idle_gaps"]["unlabelled"]
    # what needs no second clock is still there
    assert report["busy_s"] == pytest.approx(4.8e-3)
    assert report["device_s_by_scope"]["gcm.ghash"] == pytest.approx(1.4e-3)


def test_a_skew_inside_the_slack_cannot_be_seen(tmp_path):
    report = profile_report.reduce(
        write_xplane(tmp_path / "slack.xplane.pb", host_shift_ms=0.15)
    )
    assert report["clock_check"]["violations"] == 0
    assert report["clock_check"]["launch_slack_us_min"] == pytest.approx(50.0)


def test_a_launch_with_no_device_work_after_it_is_a_violation():
    check = profile_report.clock_check(
        launches=[(10, 20), (110, 120)], waits=[(20, 90), (120, 190)], programs=[(30, 80)]
    )
    # the second launch and wait have no burst, and the one burst began
    # before the launch it is held against
    assert check["launches_or_waits_with_no_program"] == 2 and check["violations"] == 3
    assert check["worst_skew_us"] == pytest.approx(0.08)


def test_a_trace_with_no_launch_labels_nothing(tmp_path):
    host = [h for h in HOST if h[3] != "transform.launch"]
    report = profile_report.reduce(write_xplane(tmp_path / "x.xplane.pb", host=host))
    assert report["clock_check"]["launches"] == 0
    assert "by_span_s" not in report["idle_gaps"]


def test_a_window_program_is_known_by_its_name_where_its_scopes_are_lost(tmp_path, monkeypatch):
    """Op metadata is not in the compile-cache key: an executable that
    another checkout compiled comes back with that checkout's op names."""
    monkeypatch.setattr(profile_report, "SCOPE", profile_report.re.compile(r"no such scope"))
    report = profile_report.reduce(write_xplane(tmp_path / "x.xplane.pb"))
    check = report["clock_check"]
    assert (check["device_programs"], check["other_device_programs"]) == (3, 1)
    assert check["violations"] == 0
    assert report["device_s_by_scope"] == pytest.approx({"unscoped": 4.8e-3})


def test_where_the_trace_tells_neither_every_program_is_taken(tmp_path, monkeypatch):
    monkeypatch.setattr(profile_report, "SCOPE", profile_report.re.compile(r"no such scope"))
    monkeypatch.setattr(profile_report, "WINDOW_PROGRAM", profile_report.re.compile(r"no such name"))
    check = profile_report.reduce(write_xplane(tmp_path / "x.xplane.pb"))["clock_check"]
    assert (check["device_programs"], check["other_device_programs"]) == (4, 0)
    assert check["violations"] == 0  # a program too many only makes it lenient


def test_without_a_line_of_programs_the_bursts_of_operations_are_taken(tmp_path):
    report = profile_report.reduce(write_xplane(tmp_path / "x.xplane.pb", modules=[]))
    check = report["clock_check"]
    assert check["programs_from"] == "bursts of XLA Ops"
    assert (check["device_programs"], check["other_device_programs"]) == (3, 1)
    assert check["violations"] == 0
    assert check["launch_slack_us_min"] == pytest.approx(200.0)


def test_op_scopes_reads_the_metadata_that_profiledata_does_not_show(tmp_path):
    scopes = profile_report.op_scopes(write_xplane(tmp_path / "x.xplane.pb"))
    assert set(scopes) == {"/device:TPU:0"}
    assert scopes["/device:TPU:0"] == {
        "%fusion.1 = u8[16,4194320] fusion()": "gcm.ctr",
        "%ghash_tree_pallas.1 = u8[16,4194320] fusion()": "gcm.ghash",  # by reference
        "%copy.15 = u8[16,4194320] fusion()": "gcm.pack",
    }


def test_merge_bridges_short_gaps():
    assert profile_report.merge([(5, 6), (0, 2), (2, 3)]) == [[0, 3], [5, 6]]
    assert profile_report.merge([(5, 6), (0, 2)], bridge=3) == [[0, 6]]


def test_off_a_tpu_main_refuses():
    with pytest.raises(SystemExit, match="no TPU"):
        profile_report.main(["--mode", "copy"])


@pytest.mark.parametrize("mode,operations", [("copy", "2"), ("fetch", "6")])
def test_main_rehearsed_on_the_cpu(mode, operations, monkeypatch, capsys):
    """End to end at 64 KiB chunks, the device check answered by the test and
    the CPU client's threads taken for the device's line: no number here is a
    device's, only that the report is whole."""
    import json

    import jax

    pytest.importorskip("cryptography")
    sys.path.insert(0, str(REPO_ROOT))
    import chip_smoke

    monkeypatch.setattr(
        chip_smoke, "require_tpu",
        lambda chips=None: {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    )
    monkeypatch.setattr(
        profile_report, "is_device_line",
        lambda plane, line: plane == "/host:CPU" and line.startswith("tf_XLAPjRtCpuClient"),
    )
    before = jax.config.jax_compilation_cache_dir
    try:
        assert profile_report.main([
            "--mode", mode, "--operations", operations, "--seed", str(2**31 + 5),
            "--chunk-bytes", str(64 << 10), "--segment-bytes", str(40 * (64 << 10) - 300),
            "--read-bytes", str(16 << 10),
        ]) == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    result = json.loads(capsys.readouterr().out.splitlines()[-1])
    report = result["report"]
    assert result["mode"] == mode and result["operations"] == int(operations)
    assert report["device_ops"] > 0 and report["busy_s"] > 0
    assert report["clock_check"]["launches"] > 0
    assert report["clock_check"]["waits"] == report["clock_check"]["launches"]
    assert result["counters"]["gcm_dispatches"] == report["clock_check"]["launches"]
    assert set(report["idle_gaps"]) >= {"count", "idle_s"}
    assert {"clock_check", "idle_gaps", "device_s_by_scope"} <= set(report)
