"""Text models of the zoo: ``KeyeLM`` (indexer-selected sparse attention,
top-k experts), ``MoonlightLM`` (latent attention, bias-balanced sigmoid
routing, shared experts) and ``OuroLM`` (a dense decoder whose layers run
several times a step with the same weights, an exit gate after each pass),
each with its loss."""
from .keye_lm import KeyeLM, KeyeLMLoss
from .moonlight_lm import MoonlightLM, MoonlightLMLoss
from .ouro_lm import OuroLM, OuroLMLoss

__all__ = ["KeyeLM", "KeyeLMLoss", "MoonlightLM", "MoonlightLMLoss",
           "OuroLM", "OuroLMLoss"]
