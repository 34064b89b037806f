"""Names of the federated RL driver's layers on the device.

``run_fedrl_core`` opens a ``jax.named_scope`` with one of these names at
each layer boundary, so every op a layer lowers to carries the name in its
``op_name`` metadata (and a profiler trace in the op's ``tf_op``). The
outermost name on an op's path is its layer; ``PAYLOAD_ENCODE`` nests inside
``LOCAL_STEP`` (gossip) or ``SYNC`` (uplink), and ``TOPK_SELECT`` inside
``PAYLOAD_ENCODE``.
"""
ROLLOUT = "fed_rollout"             # env resets and experience collection
PPO_GRAD = "fed_ppo_grad"           # per-agent PPO gradients, tree view, ravel
LOCAL_STEP = "fed_local_step"       # the strategy's local step on the carry
SYNC = "fed_sync"                   # the period-boundary server sync
EVALUATE = "fed_evaluate"           # server reads and the evaluation gradient
PAYLOAD_ENCODE = "fed_payload_encode"  # payload compression, nested
TOPK_SELECT = "fed_topk_select"     # top-k threshold select, in the encode
