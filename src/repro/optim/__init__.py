from repro.optim.adamw import (AdamWConfig, AdamWState, apply_updates,
                               cosine_lr, global_norm, init_state)
from repro.optim import compression

__all__ = ["AdamWConfig", "AdamWState", "apply_updates", "cosine_lr",
           "global_norm", "init_state", "compression"]
