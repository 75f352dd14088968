from repro.core.privacy import LedgerState
from repro.fl.algorithms import (Algorithm, get_algorithm, list_algorithms,
                                 register_algorithm, unregister_algorithm)
from repro.fl.api import Trainer, TrainState
from repro.fl.bank import (BankState, ClientBank, ResidentBank,
                           StreamedBank, make_bank)
from repro.fl.client import local_train, model_update
from repro.fl.rounds import (evaluate, round_epsilon_spent, sample_cohort,
                             split_round_key)

__all__ = ["Algorithm", "BankState", "ClientBank", "LedgerState",
           "ResidentBank", "StreamedBank", "Trainer", "TrainState",
           "get_algorithm", "list_algorithms", "make_bank",
           "register_algorithm", "unregister_algorithm", "local_train",
           "model_update", "evaluate", "round_epsilon_spent",
           "sample_cohort", "split_round_key"]
