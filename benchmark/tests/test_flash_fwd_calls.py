"""``flash_fwd_calls_per_bwd`` on hand-made ops: forward calls of the
flash kernel for each dq call, whatever the kernel's variant; a slice
with no flash backward (a serve cell, a program on the reference path)
gives no number and does not raise."""

import pytest

from benchmark import cells


class FakeTrace:
    devices = ("/device:TPU:0",)

    def __init__(self, heads):
        self.ops = [(f"%{h} = bf16[2,16,4096,128]{{3,2,1,0}} custom-call()",
                     0.0, 1.0) for h in heads]

    def kernel_events(self, match):
        return [s for s in self.ops if match(s[0])]


LAYER = ["flash_dq_kernel_tri.1", "flash_dkv_kernel_tri.1", "fusion.7"]


@pytest.mark.parametrize("heads, want", [
    (["flash_fwd_kernel_tri.2"] + LAYER, 1.0),
    (["flash_fwd_kernel_tri.2", "flash_fwd_kernel_tri.3"] + LAYER, 2.0),
    (["flash_fwd_kernel.4", "flash_dq_kernel.5", "flash_dkv_kernel.5"], 1.0),
    (["flash_fwd_kernel.4", "decode_attention.9"], None),
    ([], None),
])
def test_forward_calls_for_each_backward_call(heads, want):
    read = cells.layer_reader("flash_fwd_calls_per_bwd")
    assert read("flash_fwd_calls_per_bwd", {"trace": FakeTrace(heads)}) == want


def test_no_trace_gives_no_number():
    read = cells.layer_reader("flash_fwd_calls_per_bwd")
    assert read("flash_fwd_calls_per_bwd", {"trace": None}) is None
