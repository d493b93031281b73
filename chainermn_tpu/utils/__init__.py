"""Runtime utilities: platform setup, profiling, failure detection
and recovery primitives, chaos (fault) injection, distributed LR
recipes."""

from chainermn_tpu.utils.platform import enable_compilation_cache  # noqa
from chainermn_tpu.utils.platform import force_host_devices  # noqa
from chainermn_tpu.utils import profiling  # noqa
from chainermn_tpu.utils import chaos  # noqa
from chainermn_tpu.utils.chaos import FaultInjector  # noqa
from chainermn_tpu.utils.failure import (  # noqa
    NanGuard, DivergenceError, Heartbeat, check_finite, detect_stall,
    read_heartbeat, heartbeat_extension, CommFailure, ChannelTimeout,
    PeerDeadError, ReplicaDeadError, Backoff, Deadline,
    CheckpointCorruptError,
    CheckpointSkippedWarning, exit_code_for, classify_exit)
from chainermn_tpu.utils.schedules import (  # noqa
    linear_scaled_lr, gradual_warmup, distributed_sgd_schedule)
