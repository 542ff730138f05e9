"""The device gate refuses anything but enough TPUs; peaks come only from
the table, by device kind."""
import types

import jax
import pytest

from chipbench import device


def test_gate_refuses_the_cpu():
    with pytest.raises(device.DeviceError):
        device.gate(jax.devices("cpu"), 1)


def _tpu(kind="TPU v5 lite"):
    return types.SimpleNamespace(platform="tpu", device_kind=kind)


def test_gate_refuses_too_few_chips():
    with pytest.raises(device.DeviceError):
        device.gate([_tpu()], 4)
    with pytest.raises(device.DeviceError):
        device.gate([], 1)


def test_gate_accepts_tpus():
    assert device.gate([_tpu()] * 4, 4) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4}


def test_peaks_by_kind():
    p = device.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(device.DeviceError):
        device.peaks("TPU v9 imaginary")
