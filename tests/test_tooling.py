"""Tooling gates wired into the test run.

tools/check_no_bare_except.py bans bare ``except:`` and silent
``except Exception: pass`` in tempo_tpu/ — patterns that would make
failures invisible to the resilience layer's classify/retry machinery.

tools/check_no_dynamic_gather.py bans gather/scatter-shaped calls in
the Pallas kernel modules (ops/pallas_*.py) — the primitive class
behind the dense-regime rolling regression (config 2b of the
pre-PR-1 chip bench at 8M rows/s, below one CPU core) that the
streaming window engine removed."""

import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
CHECKER = REPO / "tools" / "check_no_bare_except.py"


def test_package_has_no_bare_except():
    proc = subprocess.run(
        [sys.executable, str(CHECKER), str(REPO / "tempo_tpu")],
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, \
        f"bare-except violations:\n{proc.stdout}{proc.stderr}"


def test_checker_flags_violations(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "try:\n"
        "    x = 1\n"
        "except:\n"                      # bare
        "    raise\n"
        "try:\n"
        "    y = 2\n"
        "except Exception:\n"            # silent swallow
        "    pass\n"
        "try:\n"
        "    z = 3\n"
        "except (ValueError, Exception):\n"   # broad inside tuple, silent
        "    ...\n"
        "try:\n"
        "    w = 4\n"
        "except Exception as e:\n"       # broad but handled: allowed
        "    print(e)\n"
    )
    proc = subprocess.run(
        [sys.executable, str(CHECKER), str(bad)],
        capture_output=True, text=True,
    )
    assert proc.returncode == 1
    assert proc.stdout.count(str(bad)) == 3
    assert "bare 'except:'" in proc.stdout
    assert "silently swallows" in proc.stdout


GATHER_CHECKER = REPO / "tools" / "check_no_dynamic_gather.py"


def test_pallas_modules_have_no_dynamic_gathers():
    proc = subprocess.run(
        [sys.executable, str(GATHER_CHECKER)],
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, \
        f"dynamic-gather violations:\n{proc.stdout}{proc.stderr}"


def test_gather_lint_covers_the_chunked_merge_kernel():
    """The round-6 lane-chunked streaming kernel lives in
    ops/pallas_merge.py and must stay inside the linter's default
    sweep (VERDICT r5 "Next round" #8)."""
    import tools.check_no_dynamic_gather as g

    names = {p.name for p in g.default_paths()}
    assert "pallas_merge.py" in names
    assert not g.check_file(
        REPO / "tempo_tpu" / "ops" / "pallas_merge.py")


def test_comm_bytes_hlo_parser():
    """profiling.comm_bytes_from_compiled reads collective traffic out
    of optimized HLO text — the measured half of the dryrun's
    ``comm_bytes=model:measured`` ICI audit."""
    from tempo_tpu import profiling

    class FakeCompiled:
        def as_text(self):
            return "\n".join([
                "HloModule m",
                "  %cp.1 = f32[8,4]{1,0} collective-permute(%x), "
                "source_target_pairs={{0,1}}",
                "  ROOT %ag = (f32[2,8]{1,0}, s32[2,8]{1,0}) "
                "all-gather(%a, %b), dimensions={0}",
                "  %add = f32[8,4]{1,0} add(%cp.1, %cp.1)",
                # async decomposition: counted at the -done (its result
                # is the received data); the -start bundle is skipped
                "  %s = (f32[4,2]{1,0}, f32[4,2]{1,0}, u32[], u32[]) "
                "collective-permute-start(%y)",
                "  %d = f32[4,2]{1,0} collective-permute-done(%s)",
            ])

    got = profiling.comm_bytes_from_compiled(FakeCompiled())
    assert got["collective-permute"] == 8 * 4 * 4 + 4 * 2 * 4
    assert got["all-gather"] == 2 * 8 * 4 + 2 * 8 * 4
    assert "all-reduce" not in got


def test_gather_checker_flags_violations(tmp_path):
    bad = tmp_path / "pallas_bad.py"
    bad.write_text(
        "import jax.numpy as jnp\n"
        "def kernel(x, idx):\n"
        "    a = jnp.take_along_axis(x, idx, axis=1)\n"       # banned
        "    b = jnp.take(x, idx)\n"                          # banned
        "    c = jnp.searchsorted(x[0], idx[0])  # gather-ok: host side\n"
        "    return a, b, c\n"
    )
    proc = subprocess.run(
        [sys.executable, str(GATHER_CHECKER), str(bad)],
        capture_output=True, text=True,
    )
    assert proc.returncode == 1
    assert proc.stdout.count(str(bad)) == 2, proc.stdout
    assert "take_along_axis" in proc.stdout
    # the gather-ok marker whitelists the searchsorted line
    assert "searchsorted" not in proc.stdout


def test_dryrun_stderr_filter_drops_only_benign_lines(capfd):
    """__graft_entry__._filter_benign_stderr: the XLA:CPU AOT
    feature-mismatch spew disappears from fd 2, real warnings and a
    one-line dropped-count summary remain (VERDICT weak #6)."""
    import os

    import __graft_entry__ as ge

    with ge._filter_benign_stderr():
        os.write(2, b"E0731 cpu_aot_loader.cc:210] Loading XLA:CPU AOT "
                    b"result. Target machine feature +prefer-no-gather\n")
        os.write(2, b"W0731 a genuinely new warning\n")
    err = capfd.readouterr().err
    assert "cpu_aot_loader" not in err
    assert "genuinely new warning" in err
    assert "filtered 1 known-benign" in err


def test_dryrun_stderr_filter_disable_knob(capfd, monkeypatch):
    import os

    import __graft_entry__ as ge

    monkeypatch.setenv("TEMPO_TPU_NO_STDERR_FILTER", "1")
    with ge._filter_benign_stderr():
        os.write(2, b"cpu_aot_loader passthrough when disabled\n")
    assert "passthrough when disabled" in capfd.readouterr().err
