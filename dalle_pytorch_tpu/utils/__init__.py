from dalle_pytorch_tpu._lazy import lazy_exports

_EXPORTS = {
    "CompileCache": "compile_cache",
    "RecompileError": "compile_guard",
    "assert_no_recompiles": "compile_guard",
    "boot_fingerprint": "compile_cache",
    "cache_hit_count": "compile_guard",
    "compile_count": "compile_guard",
    "param_count": "trees",
    "save_image_grid": "images",
    "to_uint8": "images",
    "track_compiles": "compile_guard",
    "tree_bytes": "trees",
}
__getattr__, __dir__ = lazy_exports(__name__, _EXPORTS)
