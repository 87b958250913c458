from . import ops, ref
from .gossip_mix import gossip_mix_pallas
from .gossip_schedule import gossip_schedule_pallas
from .ops import gossip_apply, gossip_mix, gossip_schedule
from .ref import gossip_mix_ref, gossip_schedule_ref

__all__ = [
    "ops",
    "ref",
    "gossip_apply",
    "gossip_mix",
    "gossip_mix_pallas",
    "gossip_mix_ref",
    "gossip_schedule",
    "gossip_schedule_pallas",
    "gossip_schedule_ref",
]
