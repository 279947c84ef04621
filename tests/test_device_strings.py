"""Custom-device plugin registrar and string tensor ops.

Reference tests: test/custom_runtime/test_custom_device_*.py (plugin
load path), test/legacy_test/test_strings_lower_upper_op.py.
"""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.device import (register_custom_device,
                               register_custom_devices_from_env,
                               get_all_custom_device_type)
from paddle_tpu import strings


def test_register_custom_device_missing_lib():
    with pytest.raises(FileNotFoundError):
        register_custom_device("mychip", "/nonexistent/pjrt_mychip.so")
    assert "mychip" not in get_all_custom_device_type()


def test_register_after_backend_init_refuses(tmp_path):
    # conftest already initialized the CPU backend -> must refuse with
    # actionable guidance instead of silently never taking effect
    fake = tmp_path / "pjrt_fake.so"
    fake.write_bytes(b"\x7fELF")
    with pytest.raises(RuntimeError, match="before JAX backends"):
        register_custom_device("fakechip", str(fake))


def test_set_device_lands_on_the_device_it_names():
    """No fallback to "whatever exists" and no index clamping: on this
    CPU rig 'tpu' is an error, not the CPU, and 'cpu:99' is not cpu:0."""
    from paddle_tpu import framework as fw
    prev = fw.get_device()
    try:
        assert fw.set_device("cpu:1").id == 1
        assert fw.get_device() == "cpu:1"
        with pytest.raises(ValueError, match="0 'tpu' device"):
            fw.set_device("tpu")
        with pytest.raises(ValueError, match="'cpu' device"):
            fw.set_device("cpu:99")
        assert fw.get_device() == "cpu:1"     # a refusal moves nothing
        with pytest.raises(ValueError, match="out of range"):
            pt.CUDAPlace(99).jax_device()
    finally:
        fw.set_device(prev)


def test_register_from_env_empty(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_CUSTOM_DEVICES", raising=False)
    assert register_custom_devices_from_env() == []


def test_strings_lower_upper():
    st = strings.to_string_tensor(["Hello World", "ABC", "already lower"])
    low = strings.lower(st)
    assert low.tolist() == ["hello world", "abc", "already lower"]
    up = strings.upper(st)
    assert up.tolist() == ["HELLO WORLD", "ABC", "ALREADY LOWER"]
    # ascii mode leaves non-ascii untouched; utf8 mode folds it
    st2 = strings.to_string_tensor(["Straße", "ÀÉÎ"])
    assert strings.lower(st2).tolist() == ["straße", "ÀÉÎ"]
    assert strings.lower(st2, use_utf8_encoding=True).tolist() == \
        ["straße", "àéî"]


def test_strings_roundtrip_device_bridge():
    st = strings.to_string_tensor(["tok", "tokenizer", "日本語"])
    codes, lens = strings.encode_utf8(st)
    assert codes.shape[0] == 3 and codes.dtype == np.uint8
    back = strings.decode_utf8(codes, lens)
    assert back.tolist() == ["tok", "tokenizer", "日本語"]
    assert strings.equal(st, back).all()


def test_strings_maxlen_truncates_on_char_boundary():
    st = strings.to_string_tensor(["日本語"])  # 9 utf-8 bytes
    codes, lens = strings.encode_utf8(st, maxlen=4)
    assert int(np.asarray(lens.data)[0]) == 3  # backed off mid-char cut
    assert strings.decode_utf8(codes, lens).tolist() == ["日"]


def test_string_tensor_validates():
    with pytest.raises(TypeError):
        strings.StringTensor([1, 2, 3])
