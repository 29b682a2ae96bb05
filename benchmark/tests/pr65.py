"""What PR 65 (a ``benchmark`` PR, the only kind that may) changed in the
data the benchmark already had: one per-layer metric a reader, where each
configuration had brought a copy of it under a prefix of its own.

``RETIRED``: the entry that went -> the shared entry that lists its cell
now (``None``: read by nothing; ``collective_all_*`` have read the classes
since PR 37). ``RETIRED_FILES`` and ``REWRITTEN``: the data files that went
with them and those whose text changed, which the tests of earlier PRs that
hold "every file the benchmark had is as it was" against their own parents
leave out. ``COSTS``: what each deleted cost file returned for its cell
(``flops``, ``bytes``), which the shared function is held to, to the last
digit. ``LISTED``: the set of metrics each cell lists by name.
"""

_EXPERTS = ("lfm2", "xing", "kimi", "laguna", "mellum", "kimivl")
RETIRED = {
    **{f"{p}_experts_ms": "experts_ms" for p in _EXPERTS},
    **{f"{p}_experts_roofline": "experts_roofline" for p in _EXPERTS},
    **{f"{p}_experts_time_share_pct": "experts_time_share_pct"
       for p in ("lfm2", "xing")},
    "mellum_moe_imbalance": "moe_imbalance",
    **{f"{p}_local_routes_pct": "local_routes_pct"
       for p in ("lfm2", "xing", "kimi", "laguna", "kimivl")},
    **{f"{p}_mlp_ms": "mlp_ms" for p in ("kimi", "laguna", "kimivl")},
    **{f"{p}_moe_route_ms": "moe_route_ms" for p in ("laguna", "mellum")},
    **{f"{p}_moe_{part}_ms": f"moe_{part}_ms"
       for p in ("laguna", "mellum", "kimivl")
       for part in ("dispatch", "combine")},
    **{f"{p}_latent_proj_ms": "latent_proj_ms"
       for p in ("xing", "kimi", "kimivl")},
    **{f"{p}_{name}": name for p in ("laguna", "mellum")
       for name in ("window_core_ms", "window_roofline", "full_core_ms")},
    "mellum_collective_all_ms": "collective_all_ms",
    "mellum_collective_all_exposed_pct": "collective_all_exposed_pct",
    "collective_ms": None, "collective_exposed_pct": None,
}
_M = "benchmark/layer_metrics/"
RETIRED_FILES = frozenset(
    [f"{_M}{name}.json" for name in RETIRED]
    + [f"{_M}{p}_experts_cost.py" for p in _EXPERTS]
    + [f"{_M}{p}_window_cost.py" for p in ("laguna", "mellum")])
REWRITTEN = frozenset(
    [f"benchmark/configs/{name}.json" for name in (
        "olmoe-1b-7b-d1", "lfm2-24b-a2b-ep8", "xing4.0-29b-a4b-ep8",
        "kimi-linear-48b-a3b-ep32", "laguna-s-2.1-ep32",
        "mellum2-12b-a2.5b-p1", "kimi-vl-a3b-ep8")]
    + [_M + name for name in (
        "experts_cost.py", "experts_ms.json", "experts_roofline.json",
        "experts_time_share_pct.json", "moe_imbalance.json", "mlp_ms.json",
        "stall_pct.json", "granite_scopes.py", "kimi_scopes.py",
        "kimivl_scopes.py", "laguna_scopes.py", "lfm2_gauges.py",
        "mellum_scopes.py", "xing_scopes.py")])

# cell -> (the cost file that went, flops, bytes) as PR 65's parent
# returned them for the cell's own sizes and sequences a step
COSTS = {
    "experts_step_cost": {
        "olmoe_c1_s4k": ("experts_cost.py", 4947802324992, 15300820992),
        "lfm2moe_c1_s8k": ("lfm2_experts_cost.py", 1855425871872.0,
                           5335154688.0),
        "xing4_c1_b1_s4k": ("xing_experts_cost.py", 676424318976.0,
                            3271526400.0),
        "kimilin_c1_b1_s8k": ("kimi_experts_cost.py", 347892350976.0,
                              1736441856.0),
        "laguna_c1_b1": ("laguna_experts_cost.py", 579820584960.0,
                         2378170368.0),
        "mellum2_c4_ep4": ("mellum_experts_cost.py", 19481971654656,
                           32463912960),
        "kimivl_c1_b1_s4k": ("kimivl_experts_cost.py", 637802643456.0,
                             2274361344.0)},
    "window_step_cost": {
        "laguna_c1_b1": ("laguna_window_cost.py", 1572862427136.0,
                         3034054656),
        "mellum2_c4_ep4": ("mellum_window_cost.py", 2525793091584.0,
                           2730491904)},
}

_EXPERT_METRICS = {"experts_ms", "experts_time_share_pct",
                   "experts_roofline"}
_ALONG = {"moe_route_ms", "moe_dispatch_ms", "moe_combine_ms"}
_SKEW = {"chip_skew_ms", "collective_all_ms", "collective_all_exposed_pct",
         "collective_transfer_ms", "collective_wait_ms"}
LISTED = {
    "gpt2xl_c1_b16": {"mlp_ms"}, "gpt2xl_c1_b4": {"mlp_ms"},
    "mistral7b_c1_s4k": {"mlp_ms"},
    "mistral7b_c4_tp2dp2z3": _SKEW | {"collective_overlapped_ms", "mlp_ms"},
    "olmoe_c1_s4k": _EXPERT_METRICS | _ALONG | {"moe_imbalance"},
    "lfm2moe_c1_s8k": _EXPERT_METRICS | _ALONG | {
        "lfm2_moe_imbalance", "local_routes_pct", "mlp_ms", "short_conv_ms"},
    "granite4h_c1_b1": {
        "granite_ssd_ms", "granite_ssd_time_share_pct",
        "granite_ssd_roofline", "granite_mamba_ms", "mlp_ms",
        "scan_mosaic_calls"},
    "xing4_c1_b1_s4k": _EXPERT_METRICS | _ALONG | {
        "xing_hc_ms", "xing_hc_time_share_pct", "xing_mtp_ms",
        "xing_moe_imbalance", "latent_proj_ms", "local_routes_pct",
        "mlp_ms"},
    "kimilin_c1_b1_s8k": _EXPERT_METRICS | {
        "kimi_kda_ms", "kimi_kda_time_share_pct", "kimi_kda_mixer_ms",
        "kimi_kda_roofline", "kimi_moe_imbalance", "latent_proj_ms",
        "local_routes_pct", "mlp_ms", "scan_mosaic_calls"},
    "laguna_c1_b1": _EXPERT_METRICS | _ALONG | {
        "laguna_gate_ms", "laguna_band_tiles_pct", "laguna_moe_imbalance",
        "window_core_ms", "window_roofline", "full_core_ms",
        "local_routes_pct", "mlp_ms"},
    "mellum2_c4_ep4": _EXPERT_METRICS | _ALONG | _SKEW | {
        "mellum_exchange_ms", "mellum_exchange_exposed_pct",
        "mellum_exchange_wait_ms", "mellum_exchange_transfer_ms",
        "mellum_chip_imbalance", "mellum_step_passes",
        "mellum_fullest_chip_pct", "moe_imbalance", "window_core_ms",
        "window_roofline", "full_core_ms"},
    "kimivl_c1_b1_s4k": _EXPERT_METRICS | {
        "kimivl_tower_ms", "kimivl_tower_share_pct", "kimivl_tower_core_ms",
        "kimivl_tower_core_roofline", "kimivl_tower_pairs_pct",
        "kimivl_tower_mlp_ms", "kimivl_merge_project_ms",
        "kimivl_place_images_ms", "kimivl_moe_imbalance", "latent_proj_ms",
        "local_routes_pct", "mlp_ms", "moe_dispatch_ms", "moe_combine_ms"},
    "phi4flash_c1_b1": {
        "selective_scan_ms", "selective_scan_time_share_pct",
        "selective_scan_roofline", "mamba1_mixer_ms", "gmu_ms",
        "attn_diff_ms", "cross_core_ms", "window_core_ms", "full_core_ms",
        "mlp_ms", "scan_mosaic_calls"},
}
