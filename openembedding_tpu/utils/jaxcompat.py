"""The one JAX result this repo reads through a name of its own.

The code targets the installed JAX (0.9.0) directly: ``jax.shard_map``,
``jax.enable_x64``, ``jax.config.update("jax_num_cpu_devices", n)``.
What is left here is a reduction, not a version bridge.
"""

from __future__ import annotations


def compiled_memory_stats(compiled):
    """``compiled.memory_analysis()`` as a plain dict, or None where the
    backend reports no analysis.

    Keys: ``argument_bytes``, ``output_bytes``, ``temp_bytes``,
    ``alias_bytes``, ``generated_code_bytes``, plus the derived
    ``peak_bytes`` (= argument + output + temp - alias, the standard
    per-device live-memory estimate for one program invocation).
    """
    stats = compiled.memory_analysis()
    if stats is None:
        return None
    out = {
        "argument_bytes": int(stats.argument_size_in_bytes),
        "output_bytes": int(stats.output_size_in_bytes),
        "temp_bytes": int(stats.temp_size_in_bytes),
        "alias_bytes": int(stats.alias_size_in_bytes),
        "generated_code_bytes": int(stats.generated_code_size_in_bytes),
    }
    out["peak_bytes"] = max(
        0, out["argument_bytes"] + out["output_bytes"] + out["temp_bytes"]
        - out["alias_bytes"])
    return out
