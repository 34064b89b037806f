"""``mfu.train``: the whole training step's share of the chips' bf16 peak.

Model FLOPs per agent env-step, from the policy's shapes: each RL vehicle's
actor and critic forward passes in the rollout, and again forward and
backward (twice the forward) in the PPO update; times the env-steps per
second of the traced run, over the chips' peak. The environment's own
arithmetic, the bootstrap values and the per-epoch evaluation are not
model FLOPs and are not counted.
"""
LAYER = "driver"
UNIT = "%"
SOURCE = "host_clock"
MOVES = "env_steps_per_s"


def forward_flops(policy: dict) -> int:
    """FLOPs of one actor and one critic forward pass on one observation."""
    o, h, a = policy["obs_dim"], policy["hidden"], policy["act_dim"]
    macs = (o * h + h * h + h * a) + (o * h + h * h + h)
    return 2 * macs


def flops_per_env_step(cfg: dict) -> int:
    """Rollout forward + update forward and backward, per RL vehicle."""
    return 4 * forward_flops(cfg["policy"]) * len(cfg["env"]["rl_indices"])


def read(out, reduced, cell, peaks):
    rate = out.end_to_end["env_steps_per_s"]
    chips = out.layer["chips"]
    return (100.0 * flops_per_env_step(cell.config) * rate
            / (chips * peaks["bf16_flops_per_s"]))
