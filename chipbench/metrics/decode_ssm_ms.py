"""Model step: device time per decode program call of the ops whose scope
path lies under ``ssm`` (the Mamba2 mixers, their state and conv window
updates inside), from the trace. Each op's scope path is read from the
compiled decode program's HLO (``program_trace.add_op_scopes``)."""
from chipbench import program_trace


def _scoped_hlo(fleet) -> str:
    """The decode program's compiled HLO with this build's scopes. The
    program that ran may have come from a compilation cache keyed without
    the op metadata, filled by a build of other scopes, and jit keeps that
    executable in memory: so the reader drops jit's caches and compiles
    again with the metadata in the key. Op names do not depend on the
    metadata, so they match the trace. Call it once the window is over."""
    import jax

    from chipbench.program_parts import decode_hlo

    key = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, key)
    jax.config.update(key, True)
    jax.clear_caches()
    try:
        return decode_hlo(fleet)
    finally:
        jax.config.update(key, was)


def ssm_times(run):
    """(decode calls, {scope path: device seconds}) of the traced window's
    decode programs, with the op scopes put into the trace first; None where
    there is no trace or no op of the program lies under ``ssm``."""
    red = run.reduced
    if red is None or not red.chips:
        return None
    if not any("op_scopes" in dev for dev in red.data["devices"].values()):
        program_trace.add_op_scopes(red.data, [_scoped_hlo(run.fleet)])
    calls, by_path = program_trace.scope_times(red)
    if not calls or not any("ssm" in p.split("/") for p in by_path):
        return None
    return calls, by_path


def read(run):
    got = ssm_times(run)
    if got is None:
        return None
    calls, by_path = got
    return 1e3 * sum(v for p, v in by_path.items() if "ssm" in p.split("/")) / calls
