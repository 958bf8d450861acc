"""The harness refuses every device but a TPU whose kind has published peaks.

The check is run on stand-in device descriptions: a child process that loads
the TPU runtime would collide with the test worker that holds its lock.
"""

from types import SimpleNamespace

import pytest

from chipbench import peaks, run


def dev(platform="tpu", kind="TPU v5 lite"):
    return SimpleNamespace(platform=platform, device_kind=kind)


def test_accepts_v5e():
    assert run.check_devices([dev()], 1)["bf16_flops"] == 197e12


def test_refuses_cpu():
    with pytest.raises(run.DeviceError, match="no TPU"):
        run.check_devices([dev("cpu", "cpu")], 1)


def test_refuses_no_device():
    with pytest.raises(run.DeviceError, match="no TPU"):
        run.check_devices([], 1)


def test_refuses_unknown_kind():
    with pytest.raises(run.DeviceError, match="not in chipbench/peaks.py"):
        run.check_devices([dev(kind="TPU v9 imaginary")], 1)


def test_refuses_too_few_chips():
    with pytest.raises(run.DeviceError, match="needs 4"):
        run.check_devices([dev()], 4)


def test_peaks_table_names_its_source():
    for kind, entry in peaks.PEAKS.items():
        assert entry["source"] and entry["bf16_flops"] > 0 and entry["hbm_bytes_per_s"] > 0
