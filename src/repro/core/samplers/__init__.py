from repro.core.samplers.base import (  # noqa: F401
    ExactArgmaxQueue,
    NoisyMaxQueue,
    Queue,
)
from repro.core.samplers.fib_heap import FibonacciHeap, FibHeapQueue  # noqa: F401
from repro.core.samplers.bsls import BSLSSampler  # noqa: F401
from repro.core.samplers.bsls_jax import TwoLevelSamplerState, tl_init, tl_rebuild, tl_sample, tl_update  # noqa: F401
from repro.core.samplers.group_argmax import GroupArgmaxState, ga_init, ga_get_next, ga_rebuild, ga_update  # noqa: F401
