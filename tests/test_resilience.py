"""Failure classification and retry/backoff units (tempo_tpu/resilience.py).

Driven with fake clocks/sleeps so the backoff schedule itself is
asserted, not just the outcomes."""

import errno
import logging
import random
import zipfile

import pytest

from tempo_tpu import resilience
from tempo_tpu.resilience import (
    CheckpointError,
    DeadlineExceeded,
    FailureKind,
    RetryPolicy,
    classify,
    retrying,
)
from tempo_tpu.testing import faults


class TestClassify:
    def test_transient_errnos(self):
        assert classify(OSError(errno.EIO, "io")) is FailureKind.TRANSIENT_IO
        assert classify(OSError(errno.ECONNRESET, "rst")) is \
            FailureKind.TRANSIENT_IO
        assert classify(ConnectionResetError()) is FailureKind.TRANSIENT_IO

    def test_missing_file_is_permanent(self):
        assert classify(FileNotFoundError(errno.ENOENT, "gone", "f")) is \
            FailureKind.PERMANENT

    def test_corruption(self):
        assert classify(zipfile.BadZipFile("bad crc")) is \
            FailureKind.CORRUPTED_ARTIFACT
        assert classify(EOFError()) is FailureKind.CORRUPTED_ARTIFACT
        assert classify(CheckpointError("checksum mismatch")) is \
            FailureKind.CORRUPTED_ARTIFACT

    def test_compile_oom_heuristics(self):
        assert classify(RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory allocating 123 bytes"
        )) is FailureKind.COMPILE_OOM
        assert classify(RuntimeError("LLVM: Cannot allocate memory")) is \
            FailureKind.COMPILE_OOM
        assert classify(MemoryError("host budget")) is FailureKind.COMPILE_OOM

    def test_device_loss_heuristics(self):
        assert classify(RuntimeError("DEVICE_LOST: chip halted")) is \
            FailureKind.DEVICE_LOSS

    def test_deadline(self):
        assert classify(TimeoutError("no")) is FailureKind.DEADLINE
        assert classify(RuntimeError("DEADLINE_EXCEEDED: barrier")) is \
            FailureKind.DEADLINE

    def test_socket_timeout_is_transient_not_deadline(self):
        """Python surfaces OSError(ETIMEDOUT) AS TimeoutError; a socket
        timeout is retryable weather, unlike a logical deadline."""
        e = OSError(errno.ETIMEDOUT, "connection timed out")
        assert isinstance(e, TimeoutError)
        assert classify(e) is FailureKind.TRANSIENT_IO

    def test_explicit_attribute_wins(self):
        e = RuntimeError("looks permanent")
        e.failure_kind = FailureKind.TRANSIENT_IO
        assert classify(e) is FailureKind.TRANSIENT_IO
        assert classify(faults.InjectedFault()) is FailureKind.TRANSIENT_IO

    def test_unknown_is_permanent(self):
        assert classify(ValueError("bug")) is FailureKind.PERMANENT


class TestRetrying:
    def _retry(self, policy, sleeps, t=None):
        clock_state = t if t is not None else {"now": 0.0}

        def sleep(s):
            sleeps.append(s)
            clock_state["now"] += s

        return retrying(policy, sleep=sleep,
                        clock=lambda: clock_state["now"],
                        rng=random.Random(0))

    def test_two_failures_then_success(self, caplog):
        policy = RetryPolicy(max_attempts=4, base_delay_s=0.1,
                             max_delay_s=10.0, jitter=0.0)
        sleeps = []
        calls = {"n": 0}

        @self._retry(policy, sleeps)
        def op():
            calls["n"] += 1
            if calls["n"] <= 2:
                raise faults.InjectedFault(f"flake #{calls['n']}")
            return "ok"

        with caplog.at_level(logging.WARNING, logger="tempo_tpu.resilience"):
            assert op() == "ok"
        assert calls["n"] == 3
        # exponential backoff, jitter disabled: 0.1 then 0.2
        assert sleeps == pytest.approx([0.1, 0.2])
        retries = [r for r in caplog.records if "retrying" in r.message]
        assert len(retries) == 2

    def test_backoff_is_bounded_and_jittered(self):
        policy = RetryPolicy(max_attempts=6, base_delay_s=1.0,
                             max_delay_s=3.0, jitter=0.5)
        sleeps = []

        @self._retry(policy, sleeps)
        def op():
            raise faults.InjectedFault()

        with pytest.raises(faults.InjectedFault):
            op()
        assert len(sleeps) == 5
        assert all(0 < s <= 3.0 for s in sleeps)

    def test_non_retryable_raises_immediately(self):
        sleeps = []

        @self._retry(RetryPolicy(max_attempts=5), sleeps)
        def op():
            raise ValueError("a bug, not weather")

        with pytest.raises(ValueError):
            op()
        assert sleeps == []

    def test_corruption_is_not_retried(self):
        sleeps = []

        @self._retry(RetryPolicy(max_attempts=5), sleeps)
        def op():
            raise CheckpointError("checksum mismatch for array 'ts'")

        with pytest.raises(CheckpointError):
            op()
        assert sleeps == []

    def test_deadline_cuts_attempts_short(self):
        policy = RetryPolicy(max_attempts=100, base_delay_s=10.0,
                             jitter=0.0, deadline_s=15.0)
        sleeps = []

        @self._retry(policy, sleeps)
        def op():
            raise faults.InjectedFault()

        with pytest.raises(DeadlineExceeded):
            op()
        assert len(sleeps) == 1   # 10s slept; next 20s sleep would cross 15s

    def test_simulated_kill_never_retried(self):
        sleeps = []

        @self._retry(RetryPolicy(max_attempts=5), sleeps)
        def op():
            raise faults.SimulatedKill("SIGKILL")

        with pytest.raises(faults.SimulatedKill):
            op()
        assert sleeps == []

    def test_wraps_metadata(self):
        @retrying(RetryPolicy())
        def documented_op():
            """docstring"""

        assert documented_op.__name__ == "documented_op"


class TestMergedLanesKnob:
    def test_env_override(self, monkeypatch):
        monkeypatch.setenv("TEMPO_TPU_MAX_MERGED_LANES", "1234")
        assert resilience.max_merged_lanes() == 1234

    def test_default_sits_below_measured_compiler_oom(self, monkeypatch):
        """round-3 chip notes: the XLA sort-merge ladder OOM-killed the
        compiler at ~205K merged lanes; the default guard must trip
        before that measured cliff."""
        monkeypatch.delenv("TEMPO_TPU_MAX_MERGED_LANES", raising=False)
        assert 0 < resilience.max_merged_lanes() < 205_000


class TestFaultInjectorHarness:
    def test_flaky_restores_on_exit(self):
        import tempo_tpu.testing.faults as fmod

        original = fmod.truncate_file
        with faults.FaultInjector() as fi:
            fi.flaky(fmod, "truncate_file", failures=1)
            assert fmod.truncate_file is not original
            with pytest.raises(faults.InjectedFault):
                fmod.truncate_file("/nope")
        assert fmod.truncate_file is original
        assert [r.action for r in fi.records] == ["raise"]

    def test_kill_on_call_counts(self):
        import tempo_tpu.testing.faults as fmod

        with faults.FaultInjector() as fi:
            fi.kill_on_call(fmod, "flip_byte", call_no=2)
            with pytest.raises(TypeError):
                fmod.flip_byte()       # call 1 passes through (and fails
            with pytest.raises(faults.SimulatedKill):  # on its own args)
                fmod.flip_byte("/nope", 0)
        assert [r.action for r in fi.records] == ["pass", "kill"]
