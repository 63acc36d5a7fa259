"""Device time of a scope across programs compiled at several shapes
(``epochscope``), read by the update path's per-layer metrics."""
import pytest

import epochscope
import harness
import programtrace
import tracereduce


def _hlo(body_path: str) -> str:
    return ("HloModule jit__update_tables, x\n"
            '  %while.3 = s32[] while(), metadata={op_name="jit(_update_tables)/'
            'qbs.update/qbs.update.relabel/while"}\n'
            f'  %fusion.1 = s32[] fusion(), metadata={{op_name="{body_path}"}}\n'
            '  %fusion.2 = s32[] fusion(), metadata={op_name="jit(_update_tables)/'
            'qbs.update/scatter"}\n')


# two widths of one program: fusion.1 sits below the relabel scope in both,
# on paths that differ beneath it
WIDTH_1 = _hlo("jit(_update_tables)/qbs.update/qbs.update.relabel/while/body/gather")
WIDTH_2 = _hlo("jit(_update_tables)/qbs.update/qbs.update.relabel/while/body/select")


def _obs(epochs):
    tr = tracereduce.Trace(
        ops=[[(100, 300, "%while.3 = s32[] while()"), (150, 100, "fusion.1"),
              (500, 50, "fusion.2"), (700, 100, "fusion.9")]],
        modules=[[(100, 450, "jit__update_tables(3)"), (700, 100, "jit_other(4)")]],
        spans=[(0, 1000, "bench.window")])
    return harness.Observed(tr, (0, 0, 0, 0), [], epochs, "TPU v5 lite", 32, 20)


def test_an_op_counts_where_every_program_puts_it_inside_the_scope():
    table = epochscope.scope_sets([WIDTH_1, WIDTH_2])
    assert "qbs.update.relabel" in table[("jit__update_tables", "fusion.1")]
    # the full-path table drops the op the two widths disagree on
    assert programtrace.scope_table([WIDTH_1, WIDTH_2])[
        ("jit__update_tables", "fusion.1")] == ()
    other = WIDTH_2.replace("qbs.update/qbs.update.relabel/while/body/select",
                            "qbs.update/select")
    assert "qbs.update.relabel" not in epochscope.scope_sets(
        [WIDTH_1, other])[("jit__update_tables", "fusion.1")]


def test_scope_ms_per_epoch_from_the_live_programs(monkeypatch):
    monkeypatch.setattr(programtrace, "live_hlo_texts", lambda: [WIDTH_1, WIDTH_2])
    obs = _obs(epochs=2)
    ms = 1e-6
    # the while and its body op count once; fusion.9 is in another program
    assert epochscope.scope_ms_per_epoch(obs, "qbs.update") == \
        pytest.approx((300 + 50) * ms / 2)
    assert harness.load_reader("update.relabel_ms_per_epoch")(obs) == \
        pytest.approx(300 * ms / 2)
    assert harness.load_reader("update.device_ms_per_epoch")(obs) == \
        pytest.approx(350 * ms / 2)
    assert epochscope.scope_ms_per_epoch(_obs(epochs=0), "qbs.update") is None


def test_a_program_without_the_scope_reads_nothing(monkeypatch):
    """The parent's programs hold no update scope: the readers give
    nothing and raise nothing."""
    monkeypatch.setattr(programtrace, "live_hlo_texts", lambda: [
        WIDTH_1.replace("qbs.update", "qbs.other")])
    assert harness.load_reader("update.device_ms_per_epoch")(_obs(3)) is None
    assert harness.load_reader("update.relabel_ms_per_epoch")(_obs(3)) is None
