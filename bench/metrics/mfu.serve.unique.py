"""U-Net forwards of the requests retired in the window (every request of
the unique cell runs all T steps: T - t_cut on the server, t_cut on its
client, each over its images), times the forward's FLOPs per image from
shapes, over the window and the chips' bfloat16 peak (on a TPU a float32
dot at default precision runs as one bfloat16 pass)."""


def read(rec):
    images = rec["requests"] * rec["images_per_request"]
    flops = images * rec["cfg"]["T"] * rec["flops_per_image"]
    peak = rec["peaks"]["bf16_flops_per_s"] * rec["n_chips"]
    return 100.0 * flops / (rec["window_s"] * peak)
