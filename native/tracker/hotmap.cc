#include "tracker/hotmap.h"

#include <algorithm>
#include <cstddef>
#include <cstdio>
#include <cstring>

#include "common/log.h"

namespace fdfs {

namespace {
// Changelog retention: enough history that a client polling at the map
// cadence never falls off the delta window under normal churn.
constexpr size_t kChangelogCap = 1024;
// Untracked ledger rows below this EWMA are evicted (reads/s).
constexpr double kLedgerFloor = 0.01;
constexpr size_t kLedgerCap = 4096;
}  // namespace

std::string HotMap::HomeGroup(const std::string& key) const {
  size_t slash = key.find('/');
  return slash == std::string::npos ? std::string() : key.substr(0, slash);
}

void HotMap::NoteHeat(const std::string& node,
                      const std::vector<HeatTrailerEntry>& entries) {
  auto& prev = last_seen_[node];
  for (const HeatTrailerEntry& e : entries) {
    if (e.key.empty() || e.key.size() > kHotKeyMaxLen) continue;
    // Credit reads served off an extra replica to the home key so a
    // routed read cannot cascade-promote its own copy.
    std::string key = e.key;
    auto alias = alias_.find(key);
    if (alias != alias_.end()) key = alias->second;

    int64_t dh = e.hits;
    int64_t db = e.bytes;
    auto it = prev.find(e.key);
    if (it != prev.end()) {
      dh = e.hits - it->second.first;
      db = e.bytes - it->second.second;
      // Counter-reset clamp (the monitor.top_rates discipline): a
      // shrinking cumulative counter means the daemon restarted, so the
      // new absolute value IS the window contribution.
      if (dh < 0 || db < 0) {
        dh = e.hits;
        db = e.bytes;
      }
    }
    prev[e.key] = {e.hits, e.bytes};
    LedgerRow& row = ledger_[key];
    row.window_hits += dh;
    row.window_bytes += db;
  }
}

void HotMap::Tick(double dt_s,
                  const std::function<std::vector<std::string>(
                      const std::string& home_group, int want)>& pick_targets,
                  bool run_policy) {
  ++tick_;
  if (dt_s <= 0) dt_s = 1;
  const double alpha = cfg_.ewma_alpha;

  // Fold the window into EWMAs; decay idle keys toward zero.
  for (auto it = ledger_.begin(); it != ledger_.end();) {
    LedgerRow& row = it->second;
    double rate = static_cast<double>(row.window_hits) / dt_s;
    row.ewma = alpha * rate + (1 - alpha) * row.ewma;
    row.window_hits = 0;
    row.window_bytes = 0;
    auto entry = entries_.find(it->first);
    if (entry != entries_.end()) {
      entry->second.ewma = row.ewma;
      ++it;
    } else if (row.ewma < kLedgerFloor) {
      it = ledger_.erase(it);  // cold and untracked: forget it
    } else {
      ++it;
    }
  }

  if (!run_policy) return;

  // Demote first so a freed slot can host a new promotion this tick.
  if (cfg_.demote_threshold > 0) {
    for (auto& [key, e] : entries_) {
      if (e.state != State::kPublished) continue;
      if (e.ewma >= cfg_.demote_threshold) continue;
      e.state = State::kRetiring;
      e.retired_version = ++version_;
      e.retire_tick = tick_;
      ++demotions_total_;
      RecordChange(key, {});
      FDFS_LOG_INFO("hotmap: demote %s (ewma %.1f/s, version %lld)",
                    key.c_str(), e.ewma, static_cast<long long>(version_));
    }
  }

  if (cfg_.promote_threshold <= 0) return;
  for (const auto& [key, row] : ledger_) {
    if (row.ewma < cfg_.promote_threshold) continue;
    if (entries_.count(key) != 0) continue;
    if (static_cast<int>(entries_.size()) >= cfg_.capacity) {
      FDFS_LOG_WARN("hotmap: at capacity (%d), not promoting %s",
                    cfg_.capacity, key.c_str());
      break;
    }
    std::string home = HomeGroup(key);
    if (home.empty()) continue;
    std::vector<std::string> targets =
        pick_targets(home, cfg_.max_extra_replicas);
    if (targets.empty()) continue;  // no spare capacity: defer
    Entry e;
    e.key = key;
    e.groups = std::move(targets);
    e.state = State::kPending;
    e.ewma = row.ewma;
    std::string remote = key.substr(home.size() + 1);
    for (const std::string& g : e.groups) alias_[g + "/" + remote] = key;
    ++promotions_total_;
    FDFS_LOG_INFO("hotmap: promote %s (ewma %.1f/s) -> %zu extra group(s)",
                  key.c_str(), row.ewma, e.groups.size());
    entries_.emplace(key, std::move(e));
  }
}

std::vector<HotTask> HotMap::TasksForGroup(const std::string& group) const {
  std::vector<HotTask> out;
  for (const auto& [key, e] : entries_) {
    if (HomeGroup(key) != group) continue;
    if (e.state == State::kPending) {
      out.push_back({kHotTaskReplicate, key, e.groups});
    } else if (e.state == State::kRetiring && tick_ > e.retire_tick) {
      // One-epoch gap: the tombstone must age a full policy tick before
      // any byte is deleted, so no poller holds a dead route.
      out.push_back({kHotTaskDrop, key, e.groups});
    }
    if (out.size() >= kHotTaskMaxTasks) break;
  }
  return out;
}

bool HotMap::AckReplicate(const std::string& key,
                          const std::vector<std::string>& groups) {
  auto it = entries_.find(key);
  if (it == entries_.end() || it->second.state != State::kPending)
    return false;
  Entry& e = it->second;
  for (const std::string& g : e.groups)
    if (std::find(groups.begin(), groups.end(), g) == groups.end())
      return false;  // verified set short: keep the tasks flowing
  e.state = State::kPublished;
  e.published_version = ++version_;
  RecordChange(key, e.groups);
  FDFS_LOG_INFO("hotmap: published %s -> %zu extra group(s) (version %lld)",
                key.c_str(), e.groups.size(),
                static_cast<long long>(version_));
  return true;
}

bool HotMap::AckDrop(const std::string& key) {
  auto it = entries_.find(key);
  if (it == entries_.end() || it->second.state != State::kRetiring)
    return false;
  std::string home = HomeGroup(key);
  std::string remote = key.substr(home.size() + 1);
  for (const std::string& g : it->second.groups)
    alias_.erase(g + "/" + remote);
  entries_.erase(it);
  FDFS_LOG_INFO("hotmap: dropped %s (extra copies deleted)", key.c_str());
  return true;
}

void HotMap::RecordChange(const std::string& key,
                          const std::vector<std::string>& groups) {
  changelog_.push_back({version_, key, groups});
  if (changelog_.size() > kChangelogCap) {
    size_t drop = changelog_.size() - kChangelogCap;
    trimmed_below_ = changelog_[drop - 1].version;
    changelog_.erase(changelog_.begin(),
                     changelog_.begin() + static_cast<ptrdiff_t>(drop));
  }
}

std::string HotMap::PackWire(int64_t since_version) const {
  if (since_version >= trimmed_below_ && since_version >= 0) {
    // Delta: latest changelog record per key newer than since_version.
    std::map<std::string, const ChangeRec*> latest;
    for (const ChangeRec& c : changelog_)
      if (c.version > since_version) latest[c.key] = &c;
    std::vector<HotMapEntry> out;
    out.reserve(latest.size());
    for (const auto& [key, c] : latest) out.push_back({key, c->groups});
    return PackHotMap(version_, /*full=*/false, out);
  }
  std::vector<HotMapEntry> out;
  for (const auto& [key, e] : entries_)
    if (e.state == State::kPublished) out.push_back({key, e.groups});
  return PackHotMap(version_, /*full=*/true, out);
}

bool HotMap::AdoptFull(const std::string& body) {
  int64_t version = 0;
  bool full = false;
  std::vector<HotMapEntry> wire;
  if (!ParseHotMap(reinterpret_cast<const uint8_t*>(body.data()), body.size(),
                   &version, &full, &wire) ||
      !full)
    return false;
  entries_.clear();
  alias_.clear();
  for (HotMapEntry& w : wire) {
    std::string home = HomeGroup(w.key);
    if (home.empty()) continue;
    Entry e;
    e.key = w.key;
    e.groups = std::move(w.groups);
    e.state = State::kPublished;
    e.published_version = version;
    std::string remote = e.key.substr(home.size() + 1);
    for (const std::string& g : e.groups) alias_[g + "/" + remote] = e.key;
    entries_.emplace(e.key, std::move(e));
  }
  version_ = version;
  changelog_.clear();
  trimmed_below_ = version_;
  return true;
}

std::map<std::string, int64_t> HotMap::GroupLoad() const {
  std::map<std::string, int64_t> out;
  for (const auto& [key, e] : entries_)
    for (const std::string& g : e.groups) ++out[g];
  return out;
}

const HotMap::Entry* HotMap::Find(const std::string& key) const {
  auto it = entries_.find(key);
  return it == entries_.end() ? nullptr : &it->second;
}

int64_t HotMap::CountState(State s) const {
  int64_t n = 0;
  for (const auto& [key, e] : entries_)
    if (e.state == s) ++n;
  return n;
}

bool HotMap::Save(const std::string& path) const {
  std::string tmp = path + ".tmp";
  FILE* f = fopen(tmp.c_str(), "w");
  if (f == nullptr) return false;
  fprintf(f, "version %lld\n", static_cast<long long>(version_));
  for (const auto& [key, e] : entries_) {
    fprintf(f, "entry %s %d %.3f %lld %lld", key.c_str(),
            static_cast<int>(e.state), e.ewma,
            static_cast<long long>(e.published_version),
            static_cast<long long>(e.retired_version));
    for (const std::string& g : e.groups) fprintf(f, " %s", g.c_str());
    fprintf(f, "\n");
  }
  fclose(f);
  return rename(tmp.c_str(), path.c_str()) == 0;
}

bool HotMap::Load(const std::string& path) {
  FILE* f = fopen(path.c_str(), "r");
  if (f == nullptr) return true;  // nothing saved yet
  char line[2048];
  while (fgets(line, sizeof(line), f) != nullptr) {
    long long v = 0;
    if (sscanf(line, "version %lld", &v) == 1) {
      version_ = v;
      continue;
    }
    char key[768];
    int st = 0;
    double ewma = 0;
    long long pub = 0, ret = 0;
    int consumed = 0;
    if (sscanf(line, "entry %767s %d %lf %lld %lld%n", key, &st, &ewma, &pub,
               &ret, &consumed) != 5)
      continue;
    if (st < 0 || st > static_cast<int>(State::kRetiring)) continue;
    Entry e;
    e.key = key;
    e.state = static_cast<State>(st);
    e.ewma = ewma;
    e.published_version = pub;
    e.retired_version = ret;
    e.retire_tick = 0;  // retiring entries become droppable next tick
    const char* rest = line + consumed;
    char grp[64];
    int adv = 0;
    while (sscanf(rest, " %63s%n", grp, &adv) == 1) {
      e.groups.push_back(grp);
      rest += adv;
    }
    std::string home = HomeGroup(e.key);
    if (home.empty()) continue;
    std::string remote = e.key.substr(home.size() + 1);
    for (const std::string& g : e.groups) alias_[g + "/" + remote] = e.key;
    ledger_[e.key].ewma = e.ewma;
    entries_.emplace(e.key, std::move(e));
  }
  fclose(f);
  // No changelog survives a restart: deltas start from here, older
  // pollers get a full snapshot.
  trimmed_below_ = version_;
  FDFS_LOG_INFO("hotmap loaded: %zu entries, version %lld", entries_.size(),
                static_cast<long long>(version_));
  return true;
}

}  // namespace fdfs
