"""Text models of the zoo."""
from .keye_lm import KeyeLM, KeyeLMLoss

__all__ = ["KeyeLM", "KeyeLMLoss"]
