"""Text models of the zoo."""
from .keye_lm import KeyeLM, KeyeLMLoss
from .moonlight_lm import MoonlightLM, MoonlightLMLoss

__all__ = ["KeyeLM", "KeyeLMLoss", "MoonlightLM", "MoonlightLMLoss"]
