"""From a configuration file to the program's model. The file names the
classes; nothing here knows a configuration, an architecture or a
published key by name."""
import dataclasses
import importlib

from . import published

CHECK = "check_published"


def load_attr(dotted):
    module, _, name = dotted.rpartition(".")
    return getattr(importlib.import_module(module), name)


def published_check(config_cls):
    """The function that refuses what ``config_cls``'s model would
    silently drop of a published file: a wrong model under a real name
    is worse than none, so a class nobody wrote one for is refused too.

    The knowledge belongs to the class: its own ``check_published(cfg)``
    classmethod wins. ``published.CHECKS`` holds the rules of the
    program's classes that predate this and carry none yet, by the name
    of the class or of its nearest base (a subclass inherits them, as it
    would the method)."""
    own = getattr(config_cls, CHECK, None)
    if own is not None:
        return own
    names = [f"{c.__module__}.{c.__qualname__}" for c in config_cls.__mro__]
    for name in names:
        if name in published.CHECKS:
            return published.CHECKS[name]
    raise ValueError(
        f"{names[0]} has no {CHECK}: a config class the benchmark builds "
        f"carries a classmethod {CHECK}(cfg) that gets the whole "
        f"configuration file as a dict and raises ValueError naming each "
        f"published setting its model would silently drop")


def build_model(paddle, cfg, seed, **extra):
    """The model a configuration file describes, weights from ``seed``
    through the program's own initialiser."""
    model_cls = load_attr(cfg["model"]["class"])
    config_cls = load_attr(cfg["model"]["config_class"])
    published_check(config_cls)(cfg)
    fields = {f.name for f in dataclasses.fields(config_cls)}
    kw = {k: v for k, v in cfg.items() if k in fields}
    kw.update(extra)
    paddle.seed(int(seed) % (2 ** 31 - 1))
    return model_cls(config_cls(**kw))
