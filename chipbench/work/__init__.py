"""Work the model needs, per family, computed from the configuration's
shapes: the same whatever implements it. FLOPs count matmuls (2 per
multiply-add) at the live context; bytes count parameters at the compute
dtype (bfloat16, 2 bytes), keys and values of live positions only, and
recurrent state at its stored dtype. The LM head runs once per request in
prefill. Each function returns (flops, bytes)."""
