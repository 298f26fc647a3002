"""Environment helpers (utils/env.py): the CPU-mesh XLA flag contract
every virtual-mesh entrypoint (conftest, bench, sweeps, dryrun) relies
on."""

import os
from unittest import mock

from polyaxon_tpu.utils import cpu_mesh_xla_flags


class TestCpuMeshXlaFlags:
    def _flags(self, initial=None, **kw):
        env = {} if initial is None else {"XLA_FLAGS": initial}
        with mock.patch.dict(os.environ, env, clear=False):
            if initial is None:
                # Start clean: drop the conftest-inherited XLA_FLAGS.
                os.environ.pop("XLA_FLAGS", None)
            cpu_mesh_xla_flags(**kw)
            return os.environ["XLA_FLAGS"].split()

    def test_defaults(self):
        flags = self._flags()
        assert "--xla_force_host_platform_device_count=8" in flags
        # The installed jaxlib parses the watchdog flag (XLA CHECK-aborts
        # on an UNKNOWN flag, which is why older images gated it).
        assert ("--xla_cpu_collective_call_terminate_timeout_seconds=600"
                in flags)

    def test_watchdog_timeout_param(self):
        flags = self._flags(watchdog_timeout_s=123)
        assert ("--xla_cpu_collective_call_terminate_timeout_seconds=123"
                in flags)

    def test_device_count_param(self):
        assert "--xla_force_host_platform_device_count=4" in self._flags(
            n_devices=4)

    def test_operator_flags_win(self):
        """An operator-set value is NEVER overridden (XLA repeated-flag
        parsing is last-wins, so appending would silently defeat it)."""
        flags = self._flags(
            "--xla_cpu_collective_call_terminate_timeout_seconds=1200")
        timeouts = [f for f in flags
                    if f.startswith("--xla_cpu_collective_call_terminate")]
        assert timeouts == [
            "--xla_cpu_collective_call_terminate_timeout_seconds=1200"]

    def test_existing_device_count_kept(self):
        flags = self._flags("--xla_force_host_platform_device_count=2")
        counts = [f for f in flags
                  if f.startswith("--xla_force_host_platform")]
        assert counts == ["--xla_force_host_platform_device_count=2"]

    def test_idempotent(self):
        first = self._flags()
        with mock.patch.dict(os.environ,
                             {"XLA_FLAGS": " ".join(first)}):
            cpu_mesh_xla_flags()
            assert os.environ["XLA_FLAGS"].split() == first

    def test_unrelated_flags_preserved(self):
        flags = self._flags("--xla_dump_to=/tmp/d")
        assert "--xla_dump_to=/tmp/d" in flags


class TestTpuOverlapLibtpuArgs:
    """Same append-only contract as the XLA flags above, but for
    LIBTPU_INIT_ARGS (parallel/overlap.py's env-var twin): these are
    xla_tpu_* flags, and putting them in XLA_FLAGS CHECK-aborts a
    CPU-only jaxlib, so the helper must only ever touch
    LIBTPU_INIT_ARGS — and never when no libtpu wheel is present."""

    def _args(self, initial=None, available=True):
        from polyaxon_tpu.utils import env as env_mod

        env = {} if initial is None else {"LIBTPU_INIT_ARGS": initial}
        with mock.patch.dict(os.environ, env, clear=False), \
                mock.patch.object(env_mod, "_libtpu_available",
                                  return_value=available):
            if initial is None:
                os.environ.pop("LIBTPU_INIT_ARGS", None)
            pinned = env_mod.tpu_overlap_libtpu_args()
            return pinned, os.environ.get("LIBTPU_INIT_ARGS", "").split()

    def test_pins_all_overlap_flags(self):
        from polyaxon_tpu.utils.env import TPU_OVERLAP_INIT_ARGS

        pinned, args = self._args()
        assert pinned
        for flag in TPU_OVERLAP_INIT_ARGS:
            assert flag in args

    def test_operator_setting_wins(self):
        pinned, args = self._args(
            "--xla_tpu_enable_latency_hiding_scheduler=false")
        schedulers = [a for a in args
                      if a.startswith("--xla_tpu_enable_latency_hiding")]
        assert schedulers == [
            "--xla_tpu_enable_latency_hiding_scheduler=false"]
        assert pinned  # the OTHER flags still appended

    def test_unrelated_args_preserved(self):
        _, args = self._args("--some_operator_flag=7")
        assert "--some_operator_flag=7" in args

    def test_idempotent(self):
        _, first = self._args()
        pinned_again, second = self._args(" ".join(first))
        assert second == first
        assert not pinned_again

    def test_no_libtpu_touches_nothing(self):
        pinned, args = self._args(available=False)
        assert not pinned and args == []
        pinned, args = self._args("--keep=1", available=False)
        assert not pinned and args == ["--keep=1"]
