#!/usr/bin/env python
"""Regenerate the golden wire-format fixtures under tests/fixtures/.

Run ONLY when the serialization format intentionally changes; the committed
bytes pin paddle_pb.py's wire output so any accidental field-number/layout
drift fails tests/test_paddle_pb.py::test_golden_model_bytes.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))

import numpy as np  # noqa: E402

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu.framework import paddle_pb  # noqa: E402
from paddle_tpu.framework.serialization import program_to_desc  # noqa: E402


def build_fixture_program():
    """The canonical fixture program — exercise string/int/float/bool/list
    attrs, multiple blocks-of-one, params and data vars."""
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(prog, startup):
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            h = fluid.layers.fc(x, size=8, act="relu")
            h = fluid.layers.dropout(h, dropout_prob=0.25)
            pred = fluid.layers.fc(h, size=3, act="softmax")
    return prog, startup, pred


def main():
    fixdir = os.path.join(REPO, "tests", "fixtures")
    os.makedirs(fixdir, exist_ok=True)
    prog, _, _ = build_fixture_program()
    data = paddle_pb.desc_to_pb(program_to_desc(prog))
    with open(os.path.join(fixdir, "golden_model.pb"), "wb") as f:
        f.write(data)
    # golden LoDTensor stream (reference save_op binary format)
    arr = (np.arange(12, dtype=np.float32) / 8.0).reshape(3, 4)
    blob = paddle_pb.tensor_to_stream(arr)
    with open(os.path.join(fixdir, "golden_tensor.bin"), "wb") as f:
        f.write(blob)
    print("wrote", fixdir, len(data), "model bytes,", len(blob),
          "tensor bytes")


if __name__ == "__main__":
    main()
