"""Model FLOP/s utilization of the FL step: operations the forward and
backward passes need per token (the configuration's work model,
``work/<config["work"]>.py``, recomputation left out), times the tokens
of the traced calls, over the traced window, the chips and the chip's
peak bf16 rate. In percent."""
import harness


def read(inp):
    calls = inp.counters.get("traced_calls")
    if not calls:
        return None
    work = harness.load_module("work", inp.config["work"])
    flops = work.train_flops_per_token(inp.model, inp.traffic["seq"]) * \
        calls * inp.counters["tokens_per_call"]
    return 100.0 * flops / (inp.trace.window_s * inp.chips
                            * inp.peak["bf16_flops_per_s"])
