from .log import (LightGBMError, log_debug, log_fatal, log_info, log_warning,
                  register_logger, set_verbosity)
from .timer import Timer

__all__ = [
    "LightGBMError", "log_debug", "log_fatal", "log_info", "log_warning",
    "register_logger", "set_verbosity", "Timer",
]
