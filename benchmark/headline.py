"""The bench headline configuration, shared by the perf harnesses.

Single source of truth for the conv-lowering picks the decomposition
and sweep harnesses (step_phases, xla_flags_sweep) run under, so they
measure one lowering. The picks are the round-4 autotune winners
(builder-banked, not reproduced; source file removed in PR 21); on the
chip they are not measured.
"""

HEADLINE_ENV = {"PADDLE_TPU_CONV_IMPL": "conv",
                "PADDLE_TPU_CONV_LAYOUT": "nhwc",
                "PADDLE_TPU_CONV_S2D": "1"}
