"""The decode expert kernel against its roofline: the bytes it must read,
over its device time in the trace times the chip's peak HBM rate, in
percent.

The kernel is found by its name, ``moe_experts``, among the trace's device
operations (``run.profile["device_ops"]``); it runs in decode steps only,
once per MoE layer. It skips no expert, so a call reads every held
expert's gate, up and down matrices of its layer
(``bench/shapes_hybrid.py``'s ``expert_bytes``), and the window's calls
are its decode steps times the MoE layers."""
from bench import shapes_hybrid

KERNEL = "moe_experts"


def is_kernel(name: str) -> bool:
    """``%moe_experts.3 f32[32,4096]{...}`` and the like."""
    return name.lstrip("%_").split(".")[0].split(" ")[0] == KERNEL


def read(run):
    steps = run.data.get("steps")
    if run.profile is None or not steps:
        return None
    secs = sum(t for name, t in run.profile["device_ops"] if is_kernel(name))
    if not secs:
        return None
    w0, w1 = run.window
    calls = sum(w0 <= s < w1 for s, *_ in steps) * shapes_hybrid.moe_layers(
        run.cfg)
    need = calls * shapes_hybrid.expert_bytes(run.cfg)
    return 100.0 * need / (secs * run.peak("hbm_bytes_per_s"))
