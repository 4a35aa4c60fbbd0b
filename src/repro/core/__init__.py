"""The paper's primary contribution: common automatic offload for diverse
source-language frontends — GA loop offload + pattern-DB function-block
offload + transfer hoisting over a language-independent Region IR, behind
one pipeline (`repro.core.offload.Offloader`) and a frontend registry.
"""
from repro.core.block_offload import BlockOffloadResult, block_offload_pass
from repro.core.evaluator import (EvalStats, Evaluator, ProcessPool,
                                  fitness_factory, fitness_factory_names,
                                  last_rank_corr, record_search_meta,
                                  register_fitness_factory,
                                  transfer_cost_surrogate)
from repro.core.fitness import CostModelFitness, WallClockFitness
from repro.core.frontends import (Frontend, FitnessBundle, detect_frontend,
                                  frontend_names, get_frontend,
                                  register_frontend)
from repro.core.ga import Evaluation, GAConfig, GAResult, run_ga
from repro.core.genes import (DEFAULT_ALPHABET, VARIANT_ALPHABET, CPU, GPU,
                              GPU_FUSED, GPU_PALLAS, Destination, Device,
                              GeneCoding, MeshDestination, Site,
                              coding_from_graph, destination_names,
                              get_destination, mesh_proposals,
                              register_destination, with_mesh_destinations)
from repro.core.ir import Region, RegionGraph
from repro.core.offload import (OffloadConfig, OffloadResult, Offloader,
                                SeedBank, ga_search, phenotype_key, plan,
                                plan_offload, resolve_alphabet,
                                search_fingerprint)
from repro.core.pattern_db import Match, PatternDB, PatternRecord, default_db
from repro.core.substitution import (SubstitutedCallable, SubstitutionEngine,
                                     SubstitutionReport)
from repro.core.surrogate import (FeatureExtractor, FittedSurrogate,
                                  fit_surrogate, load_fit,
                                  spearman_rank_corr)
from repro.core.variants import (SubstitutionChoice, generic_plan_report,
                                 resolve_variant)
from repro.core.transfer_planner import Transfer, TransferPlan, plan_transfers
from repro.core.verifier import VerifyResult, verify

__all__ = [
    "BlockOffloadResult", "block_offload_pass",
    "CostModelFitness", "WallClockFitness",
    "EvalStats", "Evaluator", "ProcessPool", "transfer_cost_surrogate",
    "fitness_factory", "fitness_factory_names", "register_fitness_factory",
    "last_rank_corr", "record_search_meta",
    "Frontend", "FitnessBundle", "detect_frontend", "frontend_names",
    "get_frontend", "register_frontend",
    "Evaluation", "GAConfig", "GAResult", "run_ga",
    "DEFAULT_ALPHABET", "VARIANT_ALPHABET",
    "CPU", "GPU", "GPU_FUSED", "GPU_PALLAS",
    "Destination", "Device", "MeshDestination", "GeneCoding", "Site",
    "coding_from_graph", "destination_names", "get_destination",
    "mesh_proposals", "register_destination", "with_mesh_destinations",
    "SubstitutedCallable", "SubstitutionEngine", "SubstitutionReport",
    "SubstitutionChoice", "generic_plan_report", "resolve_variant",
    "FeatureExtractor", "FittedSurrogate", "fit_surrogate", "load_fit",
    "spearman_rank_corr",
    "Region", "RegionGraph",
    "OffloadConfig", "OffloadResult", "Offloader", "SeedBank",
    "ga_search", "phenotype_key", "plan", "plan_offload",
    "resolve_alphabet", "search_fingerprint",
    "Match", "PatternDB", "PatternRecord", "default_db",
    "Transfer", "TransferPlan", "plan_transfers",
    "VerifyResult", "verify",
]
