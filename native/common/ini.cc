#include "common/ini.h"

#include <algorithm>
#include <cctype>
#include <cstdlib>
#include <fstream>
#include <sstream>

namespace fdfs {

namespace {

std::string Trim(const std::string& s) {
  size_t b = s.find_first_not_of(" \t\r\n");
  if (b == std::string::npos) return "";
  size_t e = s.find_last_not_of(" \t\r\n");
  return s.substr(b, e - b + 1);
}

std::string DirName(const std::string& path) {
  size_t pos = path.find_last_of('/');
  return pos == std::string::npos ? std::string(".") : path.substr(0, pos);
}

std::string RealPath(const std::string& path) {
  char* r = ::realpath(path.c_str(), nullptr);
  if (r == nullptr) return path;
  std::string out(r);
  ::free(r);
  return out;
}

}  // namespace

bool IniConfig::LoadFile(const std::string& path, std::string* error) {
  std::vector<std::string> stack;
  return LoadFileInner(path, &stack, error);
}

bool IniConfig::LoadFileInner(const std::string& path,
                              std::vector<std::string>* stack,
                              std::string* error) {
  std::string real = RealPath(path);
  if (std::find(stack->begin(), stack->end(), real) != stack->end()) {
    *error = "#include cycle at " + path;
    return false;
  }
  std::ifstream in(path);
  if (!in) {
    *error = "cannot open config file: " + path;
    return false;
  }
  std::stringstream ss;
  ss << in.rdbuf();
  stack->push_back(real);
  bool ok = ParseLines(ss.str(), DirName(real), stack, error);
  stack->pop_back();
  return ok;
}

bool IniConfig::LoadString(const std::string& text, std::string* error) {
  std::vector<std::string> stack;
  return ParseLines(text, "", &stack, error);
}

bool IniConfig::ParseLines(const std::string& text, const std::string& base_dir,
                           std::vector<std::string>* stack,
                           std::string* error) {
  std::istringstream in(text);
  std::string raw;
  while (std::getline(in, raw)) {
    std::string line = Trim(raw);
    if (line.empty()) continue;
    if (line[0] == '#' || line[0] == ';') {
      static const std::string kInc = "#include";
      if (line.compare(0, kInc.size(), kInc) == 0 && line.size() > kInc.size() &&
          std::isspace(static_cast<uint8_t>(line[kInc.size()]))) {
        std::string inc = Trim(line.substr(kInc.size()));
        if (inc.empty()) continue;
        if (base_dir.empty()) {
          *error = "#include in a string config has no base directory";
          return false;
        }
        if (!LoadFileInner(base_dir + "/" + inc, stack, error)) return false;
      }
      continue;
    }
    if (line.front() == '[' && line.back() == ']') continue;  // sections flattened
    size_t eq = line.find('=');
    if (eq == std::string::npos) continue;
    std::string key = Trim(line.substr(0, eq));
    std::string value = Trim(line.substr(eq + 1));
    items_[key].push_back(value);
  }
  return true;
}

std::optional<std::string> IniConfig::Get(const std::string& key) const {
  auto it = items_.find(key);
  if (it == items_.end() || it->second.empty()) return std::nullopt;
  return it->second.back();
}

std::vector<std::string> IniConfig::GetAll(const std::string& key) const {
  auto it = items_.find(key);
  return it == items_.end() ? std::vector<std::string>{} : it->second;
}

std::string IniConfig::GetStr(const std::string& key,
                              const std::string& dflt) const {
  auto v = Get(key);
  return v.has_value() ? *v : dflt;
}

int64_t IniConfig::GetInt(const std::string& key, int64_t dflt) const {
  auto v = Get(key);
  if (!v.has_value() || v->empty()) return dflt;
  return std::strtoll(v->c_str(), nullptr, 10);
}

bool IniConfig::GetBool(const std::string& key, bool dflt) const {
  auto v = Get(key);
  if (!v.has_value() || v->empty()) return dflt;
  std::string lv = *v;
  std::transform(lv.begin(), lv.end(), lv.begin(), ::tolower);
  if (lv == "1" || lv == "yes" || lv == "true" || lv == "on") return true;
  if (lv == "0" || lv == "no" || lv == "false" || lv == "off") return false;
  return dflt;
}

int64_t IniConfig::GetBytes(const std::string& key, int64_t dflt) const {
  auto v = Get(key);
  if (!v.has_value() || v->empty()) return dflt;
  return ParseBytes(*v, dflt);
}

int64_t IniConfig::ParseBytes(const std::string& text, int64_t dflt) {
  char* end = nullptr;
  int64_t n = std::strtoll(text.c_str(), &end, 10);
  std::string suffix = Trim(end);
  std::transform(suffix.begin(), suffix.end(), suffix.begin(), ::toupper);
  if (suffix.empty() || suffix == "B") return n;
  if (suffix == "K" || suffix == "KB") return n << 10;
  if (suffix == "M" || suffix == "MB") return n << 20;
  if (suffix == "G" || suffix == "GB") return n << 30;
  if (suffix == "T" || suffix == "TB") return n << 40;
  return dflt;
}

int64_t IniConfig::GetSeconds(const std::string& key, int64_t dflt) const {
  auto v = Get(key);
  if (!v.has_value() || v->empty()) return dflt;
  char* end = nullptr;
  int64_t n = std::strtoll(v->c_str(), &end, 10);
  std::string suffix = Trim(end);
  std::transform(suffix.begin(), suffix.end(), suffix.begin(), ::tolower);
  if (suffix.empty() || suffix == "s") return n;
  if (suffix == "m") return n * 60;
  if (suffix == "h") return n * 3600;
  if (suffix == "d") return n * 86400;
  return dflt;
}

}  // namespace fdfs
