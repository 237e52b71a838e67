"""Config/flags layer and version stamping tests."""

import pytest

import spark_rapids_jni_tpu as srt
from spark_rapids_jni_tpu import config


def test_version_and_build_info():
    assert srt.__version__
    info = srt.build_info()
    assert info["version"] == srt.__version__
    assert "commit" in info


def test_flag_env_resolution(monkeypatch):
    monkeypatch.delenv("SRT_JSON_FUZZ_ROWS", raising=False)
    assert config.get("json_fuzz_rows") == 300
    monkeypatch.setenv("SRT_JSON_FUZZ_ROWS", "7")
    assert config.get("json_fuzz_rows") == 7
    monkeypatch.setenv("SRT_JSON_FUZZ_ROWS", "not-a-number")
    with pytest.warns(RuntimeWarning):
        assert config.get("json_fuzz_rows") == 300  # unparsable -> default


def test_flag_override_context():
    base = config.get("json_fuzz_rows")
    with config.override(json_fuzz_rows=5):
        assert config.get("json_fuzz_rows") == 5
    assert config.get("json_fuzz_rows") == base
    with pytest.raises(KeyError):
        config.set("no_such_flag", 1)


def test_describe_lists_all_flags():
    text = config.describe()
    for name in config.FLAGS:
        assert name in text
