"""The reader of ``attention_fused_pct`` on hand-made traces, and on the
recording of a program that runs the q-chunked scan
(``bench/testdata/gpt2-w1-spans``), where it reads nothing."""
import pytest

from bench.metrics import attention_fused_pct, attention_ms
from bench.tests.test_scope_readers import BWD, FWD, REMAT, ms, scoped, stacked
from bench.tests.test_trace import CTX, op, trace_of

KERNEL = "attention/attention_kernel"


def test_reads_the_kernel_share_of_the_attention_scope():
    tr = stacked(FWD + f"/{KERNEL}/pallas_call",
                 REMAT + f"/{KERNEL}/pallas_call",
                 BWD + f"/{KERNEL}/flash_mha_bwd_dkv_block_q_major=512/"
                 "pallas_call",
                 BWD + f"/{KERNEL}/flash_mha_bwd_dq_block_q_major=512/"
                 "pallas_call",
                 BWD + "/attention/reduce_sum",
                 FWD + "/attention/transpose",
                 FWD + "/dot_general")
    assert attention_ms.read(tr, CTX) == pytest.approx(ms(60))
    assert attention_fused_pct.read(tr, CTX) == pytest.approx(100 * 4 / 6)


def test_weighs_self_time_and_whole_segments_only():
    loop = op("%w.1 = f32[] while()", 0, 100, FWD + "/attention/while")
    inner = op("%k.2 = f32[] custom-call()", 10, 90,
               FWD + f"/{KERNEL}/pallas_call")
    near = op("%f.3 = f32[] fusion()", 100, 120,
              FWD + "/attention/attention_kernels/x")
    tr = trace_of(loop, inner, near, window=(0, 120))
    # the loop's own 20 ns and the 20 ns of a segment that only starts
    # with the scope's name count as attention outside the kernel
    assert attention_fused_pct.read(tr, CTX) == pytest.approx(100 * 80 / 120)


def test_a_kernel_scope_outside_attention_is_not_read():
    tr = stacked(FWD + "/attention_kernel/pallas_call",
                 FWD + "/attention/while/body/exp")
    assert attention_fused_pct.read(tr, CTX) is None


def test_the_scan_reads_nothing(scoped):
    # the q-chunked scan as recorded on the chip: attention ops, no kernel
    ctx = dict(CTX, steps=2)
    assert attention_ms.read(scoped, ctx) is not None
    assert attention_fused_pct.read(scoped, ctx) is None
