from .checkpoint import (CHECKPOINT_MODES, checkpoint_schedule,  # noqa: F401
                         ovh_checkpoint_period)
