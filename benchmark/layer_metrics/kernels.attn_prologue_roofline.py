"""A q or k projection's way to the attention kernels (heads-major layout,
per-head RMSNorm, rotary, cast) as a share of the HBM's peak bandwidth, in
percent: the bytes a PERFECT fusion must move, counted here from the
configuration's ``architecture`` (forward the projection's output in and the
heads out, backward the cotangent and the projection's output in and its
gradient out: 5 elements a row a head dim, over q's ``num_attention_heads``
and k's ``num_key_value_heads`` of ``head_dim``, every attention layer, at the
itemsize of ``train_job.dtype``; a block-diffusion step runs two rows a data
token; what a recomputation runs again is time and not bytes; the rotary's
tables and the scale's partial sums are the program's own and not counted)
over the device time under the ``attn_prologue`` named scope. Nothing for a
trace that holds no ``dtpu_head_prologue_*`` kernel: where XLA runs the chain
it fuses parts of it into neighbours outside the scope, and a share of the
scope's time alone would read past the peak."""

from benchmark.harness.trace import in_scope

METRIC = {"layer": "kernels", "unit": "%", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}

KERNELS = "dtpu_head_prologue_"
ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def attention_layers(architecture: dict) -> int:
    kinds = architecture.get("layer_types")
    if kinds is None:
        return architecture["layers"]
    return sum(kind.endswith("attention") for kind in kinds)


def bytes_per_token(architecture: dict, itemsize: int) -> int:
    a = architecture
    rows = 2 if "block_length" in a else 1  # a noised and a clean copy
    width = (a["num_attention_heads"] + a["num_key_value_heads"]) * a["head_dim"]
    return attention_layers(a) * rows * 5 * width * itemsize


def read(observed):
    ms = observed.per_step_ms(lambda trace: trace.scope_s("attn_prologue"))
    tokens = observed.counters.get("tokens_per_step")
    if not (ms and tokens) or not observed.trace.seconds_where(
            lambda e: e["name"].startswith(KERNELS)
            and in_scope(e["op_name"], "attn_prologue")):
        return None
    moved = bytes_per_token(
        observed.section("architecture"),
        ITEMSIZE[observed.section("train_job")["dtype"]])
    moved *= tokens / observed.device["count"]
    return 100.0 * moved / observed.peaks["hbm_bytes_per_s"] / (ms / 1e3)
