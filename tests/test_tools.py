"""tools/check_tier1_budget.py — the tier-1 wall-time guard + slow-marker
audit.  Running the audit here against the REAL test tree is the CI
enforcement: an unmarked 8-device-mesh test lands as a tier-1 failure.
"""

import subprocess
import sys
import textwrap
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "tools"))

import check_tier1_budget as guard  # noqa: E402

REPO = Path(__file__).resolve().parents[1]

_LOG_OK = textwrap.dedent("""\
    ........ [100%]
    ============ slowest 3 durations ============
    46.46s call     tests/test_obs.py::test_sweep
    12.00s call     tests/test_x.py::test_y
    0.50s setup    tests/test_x.py::test_y
    ====== 358 passed, 1 skipped in 500.27s (0:08:20) ======
""")

_LOG_OVER = _LOG_OK.replace("in 500.27s (0:08:20)", "in 850.00s (0:14:10)")


def test_parse_durations_and_total():
    rows = guard.parse_durations(_LOG_OK)
    assert rows == [(46.46, "call", "tests/test_obs.py::test_sweep"),
                    (12.0, "call", "tests/test_x.py::test_y"),
                    (0.5, "setup", "tests/test_x.py::test_y")]
    assert guard.parse_total_seconds(_LOG_OK) == 500.27


def test_projection_prefers_summary_then_durations():
    proj, src = guard.projected_tier1_seconds(_LOG_OK)
    assert proj == 500.27 and "summary" in src
    no_summary = "\n".join(l for l in _LOG_OK.splitlines()
                           if "passed" not in l)
    proj, src = guard.projected_tier1_seconds(no_summary)
    assert abs(proj - 58.96) < 1e-6 and "durations" in src
    proj, src = guard.projected_tier1_seconds("nothing useful")
    assert proj is None


def test_budget_guard_thresholds(tmp_path):
    log = tmp_path / "t1.log"
    log.write_text(_LOG_OK)
    assert guard.check_budget(log, cap=870.0, threshold=0.85) == []
    log.write_text(_LOG_OVER)
    problems = guard.check_budget(log, cap=870.0, threshold=0.85)
    assert len(problems) == 1 and "850.0s exceeds" in problems[0]
    # the hotspot hints name the heaviest test
    assert "test_obs.py::test_sweep" in problems[0]
    # a missing log is a violation (the guard must not silently pass)
    assert guard.check_budget(tmp_path / "absent.log", 870.0, 0.85)


def test_marker_audit_flags_unmarked_mesh_tests(tmp_path):
    bad = tmp_path / "test_bad.py"
    bad.write_text(textwrap.dedent("""\
        import pytest
        from blades_tpu.parallel import make_mesh

        @pytest.fixture(scope="module")
        def setup():
            mesh = make_mesh()
            return mesh

        def test_uses_fixture(setup):
            pass

        def test_direct_call():
            m = make_mesh(num_devices=8)

        @pytest.mark.slow
        def test_marked_is_fine():
            m = make_mesh()

        def test_unrelated():
            pass
    """))
    msgs = guard.audit_file(bad)
    assert len(msgs) == 2
    assert any("test_uses_fixture" in m and "fixture 'setup'" in m
               for m in msgs)
    assert any("test_direct_call" in m for m in msgs)
    # module-level pytestmark covers everything
    marked = tmp_path / "test_marked.py"
    marked.write_text("import pytest\npytestmark = pytest.mark.slow\n"
                      + bad.read_text().split("\n", 1)[1])
    assert guard.audit_file(marked) == []


def test_repo_test_tree_passes_the_audit():
    """CI enforcement: every test in THIS repo that builds the 8-device
    mesh must be slow-marked."""
    assert guard.check_markers(REPO / "tests") == []


def test_cli_end_to_end(tmp_path):
    log = tmp_path / "t1.log"
    log.write_text(_LOG_OK)
    r = subprocess.run(
        [sys.executable, str(REPO / "tools" / "check_tier1_budget.py"),
         "--log", str(log), "--tests-dir", str(REPO / "tests")],
        capture_output=True, text=True, cwd=str(REPO))
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout
    log.write_text(_LOG_OVER)
    r = subprocess.run(
        [sys.executable, str(REPO / "tools" / "check_tier1_budget.py"),
         "--log", str(log), "--budget-only"],
        capture_output=True, text=True, cwd=str(REPO))
    assert r.returncode == 1
    assert "exceeds" in r.stderr


def test_aot_train_block_reads_the_store_out_of_an_hlo_line():
    """tools/aot_train_block.py (compile-only, no device) imports, and
    its reader finds the matrix store's known bits and aliasing."""
    import aot_train_block

    line = ('  %dynamic_update_slice.3 = bf16[752,4903424]{1,0:T(8,128)(2,1)} '
            'dynamic-update-slice(%buf, %upd, %mul.761, %constant.397), '
            'metadata={op_name="jit(_train_block)/blades/store/x"}, '
            'backend_config={"indices_config":{"index_known_bits":['
            '{"zeroes":"15","ones":"0","bitwidth":"32"},'
            '{"zeroes":"4294967295","ones":"0","bitwidth":"32"}],'
            '"is_index_aligned":[false,false]},'
            '"aliasing_operands":{"lists":[{"indices":["0","4"]}]}}')
    other = line.replace("752,", "16,")
    assert aot_train_block.store_ops(line + "\n" + other, 752) == [{
        "name": "dynamic_update_slice.3", "shape": [752, 4903424],
        "layout": "{1,0:T(8,128)(2,1)}",
        "index_known_zero_bits": [15, 4294967295],
        "is_index_aligned": [False, False],
        "aliasing_operands": [{"indices": ["0", "4"]}]}]
    # A matrix of row planes (ISSUE 32): three indices, the row's a
    # runtime one, the store aligned in the two that tile.
    planes = ('  %dynamic-update-slice.1 = bf16[8,3234112,128]'
              '{2,1,0:T(8,128)(2,1)} dynamic-update-slice(%buf, %upd, %row, '
              '%c0, %c0), backend_config={"indices_config":{'
              '"index_known_bits":[],'
              '"is_index_aligned":[true,false,true]},'
              '"aliasing_operands":{"lists":[{"indices":["0"]}]}}')
    (store,) = aot_train_block.store_ops(planes + "\n" + line, 8)
    assert store["shape"] == [8, 3234112, 128]
    assert store["layout"] == "{2,1,0:T(8,128)(2,1)}"
    assert store["is_index_aligned"] == [True, False, True]


def test_aot_train_block_reads_a_fused_store_s_aliasing_off_its_fusion():
    """The store into row planes is the root of a fusion (ISSUE 32): the
    operation carries the alignment, the fusion that calls it the
    aliasing."""
    import aot_train_block

    hlo = "\n".join([
        "%fused_computation.94 (param_0.111: bf16[8,3234112,128], "
        "param_1.144: u32[], param_2.8222: bf16[413966336]) -> "
        "bf16[8,3234112,128] {",
        "  ROOT %dynamic_update_slice.6 = bf16[8,3234112,128]"
        "{2,1,0:T(8,128)(2,1)} dynamic-update-slice(%param_0.111, "
        "%bitcast.1600, %param_1.144, %constant.3827, %constant.3827), "
        'backend_config={"indices_config":{"index_known_bits":[],'
        '"is_index_aligned":[true,true,true]}}',
        "}",
        "  %bitcast_dynamic-update-slice_fusion = bf16[8,3234112,128]"
        "{2,1,0:T(8,128)(2,1)} fusion(%updates_buf.1, %copy.3222, "
        "%pad_reduce_fusion), kind=kLoop, calls=%fused_computation.94, "
        'backend_config={"aliasing_operands":{"lists":[{"indices":'
        '["0","3"]}]}}',
    ])
    (store,) = aot_train_block.store_ops(hlo, 8)
    assert store["is_index_aligned"] == [True, True, True]
    assert store["aliasing_operands"] == [{"indices": ["0", "3"]}]
