"""kernels: milliseconds a step spends in all Mosaic custom calls
(every instruction that carries ``tpu_custom_call`` in the compiled
step's HLO, whatever its stem), summed, worst device, median over
traced steps. Where it is above ``attn_kernel_ms`` a kernel of another
family runs in the step; the harness logs each stem's share."""


def read(run):
    by_stem = run.reduced_trace.get("kernel_ms_by_stem")
    if not by_stem:
        return None
    run.log("Mosaic kernels by stem: " + ", ".join(
        f"{k} {v:.3f} ms" for k, v in sorted(by_stem.items())))
    return run.reduced_trace.get("kernel_ms")
