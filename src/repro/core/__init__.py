"""The paper's primary contribution: LDPC moment-encoded robust gradient descent."""
from repro.core.ldpc import LDPCCode, make_regular_ldpc, make_ldgm
from repro.core.decoder import (
    peel_decode,
    peel_decode_adaptive,
    peel_decode_batch,
    peel_decode_batch_adaptive,
    compile_peel_schedule,
    erasure_mask_key,
    DecodeResult,
    PeelSchedule,
)
from repro.core.engine import CodedComputeEngine, blocked_epilogue
from repro.core.schedule_cache import ScheduleCache
from repro.core.density_evolution import qd_sequence, q_final, threshold
from repro.core.encoding import Moments, second_moment, encode_moment, encode_moment_blocks
from repro.core.coded_step import Scheme1, Scheme2, Scheme2Blocked, run_pgd, RunResult
from repro.core.schemes import Scheme, scheme_registry
from repro.core.straggler import (
    BernoulliStragglers,
    FixedCountStragglers,
    AdversarialStragglers,
    DelayModel,
    ScheduledDelays,
)
from repro.core.grad_agg import BucketLayout, CodedAggregator
from repro.core.padding import pad_axis_to, pad_blocks

__all__ = [
    "LDPCCode", "make_regular_ldpc", "make_ldgm",
    "peel_decode", "peel_decode_adaptive", "peel_decode_batch",
    "peel_decode_batch_adaptive", "DecodeResult",
    "compile_peel_schedule", "erasure_mask_key", "PeelSchedule",
    "CodedComputeEngine", "blocked_epilogue", "ScheduleCache",
    "qd_sequence", "q_final", "threshold",
    "Moments", "second_moment", "encode_moment", "encode_moment_blocks",
    "Scheme1", "Scheme2", "Scheme2Blocked", "run_pgd", "RunResult",
    "Scheme", "scheme_registry",
    "BernoulliStragglers", "FixedCountStragglers", "AdversarialStragglers", "DelayModel", "ScheduledDelays",
    "CodedAggregator", "BucketLayout",
    "pad_axis_to", "pad_blocks",
]
