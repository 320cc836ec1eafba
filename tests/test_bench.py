"""bench.py is a measurement path: no TPU, no number.

A CPU timing must never be written under the device metric's name, so
with no TPU the bench prints one parseable error line and exits
non-zero; and the MFU denominator is the peak of the device that ran,
never a default.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench


def test_no_tpu_is_one_error_line_and_nonzero_exit(capsys):
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code not in (0, None)
    out = capsys.readouterr().out
    assert out.count("\n") == 1
    obj = json.loads(out)
    assert obj["metric"] == bench.METRIC_NAME
    assert obj["value"] is None
    assert obj["error"] == "no_tpu"


def test_unknown_device_kind_has_no_peak():
    # The suite runs on the CPU backend, whose device_kind is not a chip
    # the table lists.
    with pytest.raises(KeyError, match="no bf16 peak"):
        bench._peak_flops()
