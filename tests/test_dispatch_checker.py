"""Device-dispatch discipline checker (ISSUE 10): closure construction,
materialization/sync/retrace/donation rules on fixtures, the seeded
regression against a COPY of the real hot-path source, and the clean
run-on-repo gate."""

from __future__ import annotations

import pathlib
import shutil
import textwrap

from tieredstorage_tpu.analysis import dispatch
from tieredstorage_tpu.analysis.core import load_project, run_analysis

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]

#: Minimal hot-path skeleton: the checker engages through the ROOT names.
SKELETON = {
    "tieredstorage_tpu/transform/tpu.py": """
        import numpy as np

        from tieredstorage_tpu.ops.gcm import gcm_window_packed

        class TpuTransformBackend:
            def transform_windows(self, windows, opts):
                for window in windows:
                    staged = self._encrypt_dispatch(window, opts)
                    yield self._encrypt_finish(staged)

            def _encrypt_dispatch(self, chunks, opts):
                packed = np.zeros((len(chunks), 32), np.uint8)
                staged = self._stage_packed(packed)
                out = self._launch_packed(opts, staged)
                return out

            def _stage_packed(self, packed):
                return packed

            def _launch_packed(self, ctx, staged):
                out = gcm_window_packed(ctx, None, staged, donate=True)
                if staged.is_deleted():
                    pass
                return out

            def _encrypt_finish(self, staged):
                return np.asarray(staged)

            def _decrypt_batch(self, chunks, opts):
                return chunks
    """,
    "tieredstorage_tpu/ops/gcm.py": """
        def gcm_window_packed(ctx, ivs, data_packed, *, donate=False):
            return data_packed

        def gcm_varlen_window_packed(ctx, ivs, data_packed, lengths, *, donate=False):
            return data_packed
    """,
}


def make_project(tmp_path, files: dict[str, str]):
    for rel, src in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(src))
    return load_project(tmp_path, sorted(files))


def skeleton_with(tmp_path, **edits):
    # Replace on the RAW (pre-dedent) skeleton so anchors and insertions
    # share the literal indentation above; make_project dedents afterwards.
    files = dict(SKELETON)
    for rel, (old, new) in edits.items():
        assert old in files[rel], f"skeleton edit anchor missing: {old!r}"
        files[rel] = files[rel].replace(old, new)
    return make_project(tmp_path, files)


def run(project):
    return run_analysis(project, only=["device-dispatch"])


def details(report):
    return sorted(f.detail for f in report.findings)


class TestClosure:
    def test_repo_closure_spans_the_window_path(self):
        project = load_project(REPO_ROOT)
        closure, _, _ = dispatch.build_closure(project)
        for key in (
            "tieredstorage_tpu/transform/tpu.py:TpuTransformBackend.transform_windows",
            "tieredstorage_tpu/transform/tpu.py:TpuTransformBackend._launch_packed",
            "tieredstorage_tpu/transform/tpu.py:TpuTransformBackend._stage_packed",
            "tieredstorage_tpu/ops/gcm.py:gcm_window_packed",
            "tieredstorage_tpu/ops/gcm.py:gcm_varlen_window_packed",
            "tieredstorage_tpu/ops/gcm.py:_packed_jit",
            "tieredstorage_tpu/ops/gcm.py:_gcm_varlen_batch",
            "tieredstorage_tpu/ops/aes_bitsliced.py:ctr_keystream_batch",
            "tieredstorage_tpu/ops/ghash_pallas.py:ghash_level1_pallas",
            # ISSUE 12: the device hot-cache serve path is hot-path too — a
            # materialization there turns every "free" hit into a d2h fetch.
            "tieredstorage_tpu/fetch/cache/device_hot.py:DeviceHotCache.get_chunks",
            "tieredstorage_tpu/fetch/cache/device_hot.py:DeviceHotCache._serve_hot",
            "tieredstorage_tpu/fetch/cache/device_hot.py:DeviceHotCache.device_rows",
            "tieredstorage_tpu/fetch/cache/device_hot.py:DeviceHotCache._maybe_admit",
        ):
            assert key in closure, key

    def test_codec_modules_stay_outside(self):
        """thuff/lzhuff materialize on their own schedule — the closure must
        not cross into them even though transform_windows compresses."""
        project = load_project(REPO_ROOT)
        closure, _, _ = dispatch.build_closure(project)
        assert not any("transform/thuff.py" in k for k in closure)
        assert not any("transform/lzhuff.py" in k for k in closure)

    def test_sanctioned_inventories_match_tree(self):
        """Every sanctioned entry must name a function that still exists —
        the inventory burns down with the code it covers."""
        project = load_project(REPO_ROOT)
        closure, _, _ = dispatch.build_closure(project)
        for key in dispatch.SANCTIONED_MATERIALIZERS:
            assert key in closure, f"stale sanctioned materializer {key}"
        for key in dispatch.SANCTIONED_JIT_WRAPPERS:
            assert key in closure, f"stale sanctioned jit wrapper {key}"


class TestSeededRegression:
    """THE acceptance gate: a hidden np.asarray inserted into the REAL
    window-path source produces exactly one finding; the real tree
    produces none."""

    def _real_copy(self, tmp_path):
        for rel in (
            "tieredstorage_tpu/transform/tpu.py",
            "tieredstorage_tpu/ops/gcm.py",
            "tieredstorage_tpu/fetch/cache/device_hot.py",
        ):
            dest = tmp_path / rel
            dest.parent.mkdir(parents=True, exist_ok=True)
            shutil.copy(REPO_ROOT / rel, dest)
        return tmp_path

    def test_real_hot_path_is_clean(self):
        report = run(load_project(REPO_ROOT))
        assert report.findings == [], "\n".join(
            f.render() for f in report.findings
        )

    def test_seeded_asarray_in_window_loop_is_one_finding(self, tmp_path):
        root = self._real_copy(tmp_path)
        tpu = root / "tieredstorage_tpu/transform/tpu.py"
        src = tpu.read_text()
        anchor = "staged = self._dispatch_encrypt_window(chunks, w_opts, frame_buffer)\n"
        assert anchor in src
        line = next(line for line in src.splitlines(keepends=True) if line.endswith(anchor))
        indent = line[: len(line) - len(anchor)]
        src = src.replace(line, line + indent + "_dbg = np.asarray(staged)\n")
        tpu.write_text(src)
        report = run(load_project(root))
        assert details(report) == ["materialize:asarray"]
        (finding,) = report.findings
        assert finding.qualname == "TpuTransformBackend.transform_windows"

    def test_seeded_block_until_ready_is_caught(self, tmp_path):
        root = self._real_copy(tmp_path)
        tpu = root / "tieredstorage_tpu/transform/tpu.py"
        src = tpu.read_text()
        anchor = "out = self._launch_packed(ctx, staged, varlen, decrypt=False)\n"
        assert anchor in src
        src = src.replace(
            anchor, anchor + "        out.block_until_ready()\n", 1
        )
        tpu.write_text(src)
        report = run(load_project(root))
        assert "sync:block_until_ready" in details(report)

    def test_seeded_asarray_on_hot_serve_path_is_one_finding(self, tmp_path):
        """ISSUE 12 gate: a hidden materialization of the retained device
        rows on the hot SERVE path is a static finding."""
        root = self._real_copy(tmp_path)
        hot = root / "tieredstorage_tpu/fetch/cache/device_hot.py"
        src = hot.read_text()
        anchor = "        served = self._serve_hot(file, chunk_ids)\n"
        assert anchor in src
        src = src.replace(
            anchor,
            anchor + "        _dbg = np.asarray("
                     "self.device_rows(objects_key, chunk_ids))\n",
        )
        hot.write_text(src)
        report = run(load_project(root))
        assert details(report) == ["materialize:asarray"]
        (finding,) = report.findings
        assert finding.qualname == "DeviceHotCache.get_chunks"


class TestFusedTraceClosure:
    """ISSUE 13 checker family: the TRACE-scope closure (the packed impls
    under `_packed_jit`) statically forbids inter-stage materialization —
    the seeded acceptance gate is an injected materialization in a COPY of
    the real fused closure yielding exactly one finding, the real tree
    yielding zero."""

    def _real_copy(self, tmp_path):
        for rel in (
            "tieredstorage_tpu/transform/tpu.py",
            "tieredstorage_tpu/ops/gcm.py",
            "tieredstorage_tpu/fetch/cache/device_hot.py",
        ):
            dest = tmp_path / rel
            dest.parent.mkdir(parents=True, exist_ok=True)
            shutil.copy(REPO_ROOT / rel, dest)
        return tmp_path

    def test_trace_closure_spans_the_fused_program(self):
        project = load_project(REPO_ROOT)
        closure, _, _ = dispatch.build_closure(
            project, dispatch.TRACE_CLOSURE_ROOTS,
            stop_at=frozenset(dispatch.SANCTIONED_MATERIALIZERS),
        )
        for key in (
            "tieredstorage_tpu/ops/gcm.py:_packed_fixed_impl",
            "tieredstorage_tpu/ops/gcm.py:_packed_varlen_impl",
            "tieredstorage_tpu/ops/gcm.py:_gcm_process_batch",
            "tieredstorage_tpu/ops/gcm.py:_gcm_varlen_batch",
            "tieredstorage_tpu/ops/gcm.py:_ghash_grouped",
            "tieredstorage_tpu/ops/ghash_pallas.py:ghash_tree_pallas",
            "tieredstorage_tpu/ops/ghash_pallas.py:ghash_level1_pallas",
            "tieredstorage_tpu/ops/aes_bitsliced.py:ctr_keystream_batch",
        ):
            assert key in closure, key

    def test_stop_at_prunes_sanctioned_gate_subtrees(self):
        """The trace-time host gates (memoized preflight cross-checks)
        stay in the closure but their host-side callees do not — a
        key_expansion np.array on the context-build path must never be a
        trace-scope finding."""
        project = load_project(REPO_ROOT)
        closure, _, _ = dispatch.build_closure(
            project, dispatch.TRACE_CLOSURE_ROOTS,
            stop_at=frozenset(dispatch.SANCTIONED_MATERIALIZERS),
        )
        assert "tieredstorage_tpu/ops/aes.py:key_expansion" not in closure

    def test_sanctioned_staged_reducer_exists(self):
        project = load_project(REPO_ROOT)
        closure, _, _ = dispatch.build_closure(
            project, dispatch.TRACE_CLOSURE_ROOTS,
        )
        for key in dispatch.SANCTIONED_STAGED_REDUCERS:
            assert key in closure, f"stale sanctioned staged reducer {key}"

    def test_real_fused_closure_is_clean(self):
        report = run(load_project(REPO_ROOT))
        assert report.findings == [], "\n".join(
            f.render() for f in report.findings
        )

    def test_seeded_interstage_asarray_is_one_finding(self, tmp_path):
        """THE acceptance gate: materializing the GHASH handoff between
        stages of the real fused closure = exactly one finding."""
        root = self._real_copy(tmp_path)
        gcm = root / "tieredstorage_tpu/ops/gcm.py"
        src = gcm.read_text()
        anchor = "    t_c = _ghash_grouped(ct_padded, agg_mats, step_mat)\n"
        assert anchor in src
        src = src.replace(anchor, anchor + "    t_c = np.asarray(t_c)\n")
        gcm.write_text(src)
        report = run(load_project(root))
        assert details(report) == ["interstage:materialize:asarray"]
        (finding,) = report.findings
        assert finding.qualname == "_ghash_of_ct"

    def test_seeded_sync_in_trace_scope_is_caught(self, tmp_path):
        root = self._real_copy(tmp_path)
        gcm = root / "tieredstorage_tpu/ops/gcm.py"
        src = gcm.read_text()
        anchor = "    output = data ^ keystream\n"
        assert anchor in src
        src = src.replace(
            anchor, anchor + "    jax.block_until_ready(keystream)\n", 1
        )
        gcm.write_text(src)
        report = run(load_project(root))
        assert "interstage:sync:block_until_ready" in details(report)

    def test_seeded_unsanctioned_ladder_is_one_finding(self, tmp_path):
        """A matmul reduction loop outside the sanctioned fallback — the
        staged ladder creeping back into the fused program — is caught."""
        root = self._real_copy(tmp_path)
        gcm = root / "tieredstorage_tpu/ops/gcm.py"
        src = gcm.read_text()
        anchor = "    t_c = _ghash_grouped(ct_padded, agg_mats, step_mat)\n"
        assert anchor in src
        src = src.replace(
            anchor,
            anchor
            + "    for _w in agg_mats[1:]:\n"
            + "        t_c = jax.lax.dot_general(\n"
            + "            t_c, _w, (((1,), (0,)), ((), ())))\n",
        )
        gcm.write_text(src)
        report = run(load_project(root))
        assert details(report) == ["interstage:staged-ladder"]
        (finding,) = report.findings
        assert finding.qualname == "_ghash_of_ct"

    def test_static_params_stay_untainted(self, tmp_path):
        """int() on a static trace parameter (aad_bit_len in
        _device_len_blocks) is host arithmetic, not a materialization —
        the real closure relies on this staying clean."""
        report = run(load_project(REPO_ROOT))
        assert not any(
            f.detail.startswith("interstage") for f in report.findings
        )


class TestMaterialization:
    def test_skeleton_is_clean(self, tmp_path):
        assert run(make_project(tmp_path, SKELETON)).findings == []

    def test_tainted_asarray_flagged(self, tmp_path):
        project = skeleton_with(tmp_path, **{
            "tieredstorage_tpu/transform/tpu.py": (
                "out = self._launch_packed(opts, staged)",
                "out = self._launch_packed(opts, staged)\n"
                "                host = np.asarray(out)",
            ),
        })
        assert details(run(project)) == ["materialize:asarray"]

    def test_host_asarray_not_flagged(self, tmp_path):
        """np.asarray on host-built buffers is the packing path — legal."""
        project = skeleton_with(tmp_path, **{
            "tieredstorage_tpu/transform/tpu.py": (
                "packed = np.zeros((len(chunks), 32), np.uint8)",
                "packed = np.asarray(chunks, np.uint8)",
            ),
        })
        assert run(project).findings == []

    def test_sanctioned_finish_not_flagged(self, tmp_path):
        # _encrypt_finish already calls np.asarray on the staged window in
        # the skeleton: the sanction is what keeps the baseline clean.
        key = "tieredstorage_tpu/transform/tpu.py:TpuTransformBackend._encrypt_finish"
        assert key in dispatch.SANCTIONED_MATERIALIZERS
        assert run(make_project(tmp_path, SKELETON)).findings == []

    def test_int_on_tainted_value_flagged(self, tmp_path):
        project = skeleton_with(tmp_path, **{
            "tieredstorage_tpu/transform/tpu.py": (
                "out = self._launch_packed(opts, staged)",
                "out = self._launch_packed(opts, staged)\n"
                "                n = int(out)",
            ),
        })
        assert details(run(project)) == ["materialize:int"]

    def test_device_get_flagged_without_taint(self, tmp_path):
        project = skeleton_with(tmp_path, **{
            "tieredstorage_tpu/transform/tpu.py": (
                "return packed",
                "import jax\n"
                "                jax.device_get(packed)\n"
                "                return packed",
            ),
        })
        assert details(run(project)) == ["sync:jax.device_get"]

    def test_functions_outside_closure_not_scanned(self, tmp_path):
        project = skeleton_with(tmp_path, **{
            "tieredstorage_tpu/transform/tpu.py": (
                "def _decrypt_batch(self, chunks, opts):\n                return chunks",
                "def unrelated_helper(self, staged):\n"
                "                return np.asarray(staged).block_until_ready()",
            ),
        })
        assert run(project).findings == []


class TestRetrace:
    def test_unvetted_jit_flagged(self, tmp_path):
        project = skeleton_with(tmp_path, **{
            "tieredstorage_tpu/transform/tpu.py": (
                "out = gcm_window_packed(ctx, None, staged, donate=True)",
                "import jax\n"
                "                fn = jax.jit(lambda x: x)\n"
                "                out = gcm_window_packed(ctx, None, staged, donate=True)",
            ),
        })
        assert details(run(project)) == ["unvetted-jit"]

    def test_context_bypass_flagged(self, tmp_path):
        project = skeleton_with(tmp_path, **{
            "tieredstorage_tpu/transform/tpu.py": (
                "packed = np.zeros((len(chunks), 32), np.uint8)",
                "from tieredstorage_tpu.ops.gcm import GcmVarlenContext\n"
                "                ctx2 = GcmVarlenContext(max(len(c) for c in chunks))\n"
                "                packed = np.zeros((len(chunks), 32), np.uint8)",
            ),
        })
        assert details(run(project)) == ["shape-not-bucketed:GcmVarlenContext"]

    def test_vetted_wrapper_key_is_sanctioned(self):
        assert (
            "tieredstorage_tpu/ops/gcm.py:_packed_jit"
            in dispatch.SANCTIONED_JIT_WRAPPERS
        )


class TestDonation:
    def test_use_after_donate_flagged(self, tmp_path):
        project = skeleton_with(tmp_path, **{
            "tieredstorage_tpu/transform/tpu.py": (
                "if staged.is_deleted():\n                    pass\n                return out",
                "tail = staged[:, -16:]\n                return out",
            ),
        })
        assert details(run(project)) == ["use-after-donate:staged"]

    def test_is_deleted_probe_allowed(self, tmp_path):
        assert run(make_project(tmp_path, SKELETON)).findings == []

    def test_sibling_branch_donating_call_not_flagged(self, tmp_path):
        project = skeleton_with(tmp_path, **{
            "tieredstorage_tpu/transform/tpu.py": (
                "out = gcm_window_packed(ctx, None, staged, donate=True)",
                "if ctx:\n"
                "                    out = gcm_window_packed(ctx, None, staged, donate=True)\n"
                "                else:\n"
                "                    out = gcm_window_packed(None, None, staged, donate=True)",
            ),
        })
        assert run(project).findings == []

    def test_undonated_call_not_tracked(self, tmp_path):
        project = skeleton_with(tmp_path, **{
            "tieredstorage_tpu/transform/tpu.py": (
                "out = gcm_window_packed(ctx, None, staged, donate=True)\n"
                "                if staged.is_deleted():\n                    pass",
                "out = gcm_window_packed(ctx, None, staged)\n"
                "                tail = staged[:, -16:]",
            ),
        })
        assert run(project).findings == []
