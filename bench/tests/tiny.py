"""A tiny sage-dit for the CPU tests that drive a whole run: the
published structure at 2 layers, width 64, 8x8x4 latents."""
from bench import harness

SEED = 2 ** 33 + 12345
#: tiny-size limit on latent_err_ratio, between the program's readings
#: (0.92-1.31 on 6 seeds) and the float8 control's (5.7-11.4 on the same
#: seeds)
LIMIT = 2.5


def spec():
    s = harness.load_config("sage-dit")
    s.update(n_layers=2, d_model=64, n_heads=2, head_dim=32, d_ff=128,
             latent_size=8, cond_dim=64, cond_len=64,
             text_tower={"layers": 2, "d_model": 64, "n_heads": 4,
                         "d_ff": 256, "vocab": 258, "rope_theta": 10000.0,
                         "max_len": 64})
    return s


def cell(workload="sage-dit-100m.themed-batch"):
    c = harness.load_cell(workload)
    c.update(lead_in_s=1.0, max_groups_per_tick=2)
    c["check"] = dict(c["check"], sample=4,
                      limits={"latent_err_ratio": LIMIT})
    c["clients"] = 8
    return c


def run(workload="sage-dit-100m.themed-batch", control=False, seed=SEED):
    """One run with a window long enough to complete requests on a CPU
    shared with other test workers."""
    return harness.run(workload, seed, 8.0, False, control, spec=spec(),
                       cell=cell(workload), require_chip=False,
                       compile_cache=False, log=lambda *a: None)
