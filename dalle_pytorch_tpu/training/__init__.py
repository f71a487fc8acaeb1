from dalle_pytorch_tpu._lazy import lazy_exports

_EXPORTS = {
    "ExponentialDecay": "lr",
    "ReduceLROnPlateau": "lr",
    "TrainState": "steps",
    "get_learning_rate": "steps",
    "make_clip_train_step": "steps",
    "make_dalle_train_step": "steps",
    "make_lm_train_step": "steps",
    "make_multi_step": "steps",
    "make_optimizer": "steps",
    "make_vae_train_step": "steps",
    "set_learning_rate": "steps",
    "stack_batches": "steps",
    "window_iter": "steps",
    "window_keys": "steps",
}
__getattr__, __dir__ = lazy_exports(__name__, _EXPORTS)
