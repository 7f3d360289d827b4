"""Wall seconds of the program's own first step call: the gauge
``lm.step.build_seconds``, set by the bracket ``obs.building("lm.step")`` in
``TransformerLM.fit_batch`` from ``_build_step`` to the return of the first
call of the jitted step (trace, lowering, compile or retrieval, and the
dispatch). What of ``setup_s`` the program owns, against the benchmark's
weights, helper programs, checked steps and reference. None where the program
has no such gauge. Layer: build."""


def read(ctx):  # noqa: ARG001 -- the readers' signature
    from deeplearning4j_tpu import obs
    try:
        return obs.metrics.value("lm.step.build_seconds") or None
    except KeyError:
        return None
